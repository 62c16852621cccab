"""The Verify flow-suspension point (VERDICT r3 #2).

Reference semantics: flows await the TransactionVerifierService future by
parking the fiber (FlowStateMachineImpl.kt:379-393, Services.kt:544-550) —
the SMM resumes them when the (possibly out-of-process) result arrives.
Covers: N concurrent flows coalescing into ONE device batch, the
OutOfProcess backend reachable from the flow path, restart-mid-verify
replay, and original-exception-type delivery at the yield site.
"""
import time
from concurrent.futures import Future

import pytest

from corda_tpu.core.contracts import Command, TransactionState
from corda_tpu.core.crypto import generate_keypair
from corda_tpu.core.crypto.signatures import SignatureException
from corda_tpu.core.identity import Party
from corda_tpu.core.transactions import WireTransaction
from corda_tpu.flows.api import FlowLogic, Verify
from corda_tpu.testing import (DUMMY_NOTARY_NAME, DummyContract, DummyState,
                               MockNetwork, MockServices)
from corda_tpu.verifier import SignatureBatcher, TpuTransactionVerifierService
from corda_tpu.verifier.out_of_process import (
    OutOfProcessTransactionVerifierService, VerifierWorker)

NOTARY_KP = generate_keypair(entropy=b"\x31" * 32)
NOTARY = Party(DUMMY_NOTARY_NAME, NOTARY_KP.public)
ALICE_KP = generate_keypair(entropy=b"\x32" * 32)


def make_issue_stx(services, i=7):
    wtx = WireTransaction(
        outputs=(TransactionState(DummyState(i, (ALICE_KP.public,)), NOTARY),),
        commands=(Command(DummyContract.Create(), (ALICE_KP.public,)),),
        notary=NOTARY, must_sign=(ALICE_KP.public,))
    return services.sign_transaction(wtx, ALICE_KP.public)


class VerifyFlow(FlowLogic):
    """Minimal flow that suspends on transaction verification."""

    def __init__(self, stx):
        self.stx = stx

    def call(self):
        yield Verify(self.stx)
        return "verified"


class CatchingVerifyFlow(FlowLogic):
    def __init__(self, stx):
        self.stx = stx

    def call(self):
        try:
            yield Verify(self.stx)
        except SignatureException:
            return "caught-signature-exception"
        return "verified"


def make_network_node():
    network = MockNetwork()
    node = network.create_node("O=Alice, L=London, C=GB")
    network.start_nodes()
    return network, node


def seed_services(node):
    """Signing services for building the test transactions (the node's own
    hub resolves/verifies them — issue transactions have no inputs)."""
    return MockServices(key_pairs=[NOTARY_KP, ALICE_KP], parties=[NOTARY])


def test_n_flows_one_device_batch():
    """N concurrently-suspended flows' signatures coalesce into ONE device
    batch — the cross-flow batching the suspension point exists for
    (impossible while flows blocked the node thread one at a time)."""
    network, node = make_network_node()
    svcs = seed_services(node)
    # verify_signed submits on the INTERACTIVE class (PR 6), so the
    # cross-flow coalescing window is interactive_latency_s now
    batcher = SignatureBatcher(host_crossover=0, max_latency_s=0.25,
                               interactive_latency_s=0.25)
    node.services.verifier_service = TpuTransactionVerifierService(
        batcher=batcher)
    try:
        fsms = [node.start_flow(VerifyFlow(make_issue_stx(svcs, i)))
                for i in range(8)]
        # every flow parked on its verify future before any batch dispatched
        assert node.smm.awaiting_external == 8
        network.run_network()
        assert [f.result_future.result(timeout=60) for f in fsms] \
            == ["verified"] * 8
        snap = batcher.metrics.snapshot()
        assert snap["SigBatcher.DeviceBatches"]["count"] == 1
        assert snap["SigBatcher.DeviceChecked"]["count"] == 8
    finally:
        node.services.verifier_service.shutdown()


def test_oop_backend_reachable_from_flows():
    """A flow on an OutOfProcess-backed node parks on the worker round-trip
    and the verification demonstrably executes in the worker — the r3 gate
    (node/services.py) that kept flows off the OOP backend is gone."""
    network, node = make_network_node()
    svcs = seed_services(node)
    svc = OutOfProcessTransactionVerifierService(node.messaging)
    node.services.verifier_service = svc
    worker = VerifierWorker(
        network.bus.create_node("verifier-worker-1"),
        str(node.info.address))
    network.run_network()     # worker Hello handshake
    fsms = [node.start_flow(VerifyFlow(make_issue_stx(svcs, i)))
            for i in range(4)]
    assert node.smm.awaiting_external == 4
    network.run_network()
    assert [f.result_future.result(timeout=30) for f in fsms] \
        == ["verified"] * 4
    assert worker.verified_count == 4


def test_verify_failure_throws_original_type_at_yield_site():
    network, node = make_network_node()
    svcs = seed_services(node)
    node.services.verifier_service = TpuTransactionVerifierService(
        batcher=SignatureBatcher(host_crossover=0, max_latency_s=0.01))
    try:
        stx = make_issue_stx(svcs)
        bad_sig = stx.sigs[0].__class__(
            stx.sigs[0].bytes[:-1] + bytes([stx.sigs[0].bytes[-1] ^ 1]),
            stx.sigs[0].by)
        bad_stx = stx.__class__(stx.tx_bits, (bad_sig,))
        fsm = node.start_flow(CatchingVerifyFlow(bad_stx))
        network.run_network()
        assert fsm.result_future.result(timeout=60) \
            == "caught-signature-exception"
    finally:
        node.services.verifier_service.shutdown()


class ManualVerifierService:
    """Async-capable verifier whose futures the test completes by hand."""

    def __init__(self):
        self.futures = []

    def verify_signed(self, stx, services, check_sufficient_signatures=True):
        fut = Future()
        self.futures.append(fut)
        return fut


def test_restart_mid_verify_replays_and_resubmits():
    """Kill the node while a flow is parked on Verify: the restored flow
    replays to the suspension point and RE-SUBMITS the verification to the
    new node's service (re-verification is idempotent — the result never
    made it into the checkpoint)."""
    network, node = make_network_node()
    svcs = seed_services(node)
    manual = ManualVerifierService()
    node.services.verifier_service = manual
    stx = make_issue_stx(svcs)
    fsm = node.start_flow(VerifyFlow(stx))
    assert len(manual.futures) == 1 and not fsm.result_future.done()
    assert node.smm.checkpoints.get_all_checkpoints()  # parked → checkpointed

    node2 = node.restart()
    manual2 = ManualVerifierService()
    node2.services.verifier_service = manual2
    seed_services(node2)
    node2.start()             # restore → replay → re-park on Verify
    assert len(manual2.futures) == 1
    restored = list(node2.smm.flows.values())[0]
    manual2.futures[0].set_result(None)
    network.run_network()
    assert restored.result_future.result(timeout=30) == "verified"
    assert not node2.smm.checkpoints.get_all_checkpoints()


def test_sync_fallback_failure_also_lands_at_yield_site():
    """The no-service fallback must deliver verification failures INTO the
    flow with their original type, exactly like the async path — not kill
    the flow from outside its except clause."""
    network, node = make_network_node()
    svcs = seed_services(node)
    assert node.services.verifier_service is None
    stx = make_issue_stx(svcs)
    bad_sig = stx.sigs[0].__class__(
        stx.sigs[0].bytes[:-1] + bytes([stx.sigs[0].bytes[-1] ^ 1]),
        stx.sigs[0].by)
    bad_stx = stx.__class__(stx.tx_bits, (bad_sig,))
    fsm = node.start_flow(CatchingVerifyFlow(bad_stx))
    network.run_network()
    assert fsm.result_future.result(timeout=30) == "caught-signature-exception"


def test_sync_fallback_without_async_service():
    """No verifier service configured → Verify verifies synchronously on the
    node thread (the no-service fallback), flows still complete."""
    network, node = make_network_node()
    svcs = seed_services(node)
    assert node.services.verifier_service is None
    fsm = node.start_flow(VerifyFlow(make_issue_stx(svcs)))
    network.run_network()
    assert fsm.result_future.result(timeout=30) == "verified"
    assert node.smm.awaiting_external == 0


def test_mesh_devices_requires_tpu_verifier():
    """Config validation (VERDICT r3 #3 follow-up): the configuration must
    FAIL AT CONSTRUCTION when mesh_devices is set with a verifier type
    that would silently ignore it — before a misconfigured node binds
    sockets or writes its identity."""
    from corda_tpu.node.node import NodeConfiguration

    for vt in ("InMemory", "OutOfProcess"):
        with pytest.raises(ValueError, match="mesh_devices requires"):
            NodeConfiguration(my_legal_name="O=Bad, L=London, C=GB",
                              verifier_type=vt, mesh_devices=4)
    # and the valid combination constructs fine
    NodeConfiguration(my_legal_name="O=Good, L=London, C=GB",
                      verifier_type="Tpu", mesh_devices=4)


class VerifyThenSleepFlow(FlowLogic):
    """Parks on Verify, then parks AGAIN on a long Sleep — the second park
    is the target a stale verify completion must not wrongly resume."""

    def __init__(self, stx):
        self.stx = stx

    def call(self):
        from corda_tpu.flows.api import Sleep
        yield Verify(self.stx)
        yield Sleep(3600)
        return "woke"


def test_stale_verify_completion_does_not_resume_wrong_park():
    """ADVICE r4 (low): _on_verify_done must check the flow is still parked
    on the ORIGINATING Verify request (like wake_timers' identity check) —
    a duplicate/stale future completion after the flow moved on must not
    resume it at the wrong yield."""
    network, node = make_network_node()
    svcs = seed_services(node)
    manual = ManualVerifierService()
    node.services.verifier_service = manual
    fsm = node.start_flow(VerifyThenSleepFlow(make_issue_stx(svcs)))
    verify_request = fsm.parked_on
    assert isinstance(verify_request, Verify)
    manual.futures[0].set_result(None)
    network.run_network()        # verify resumes; flow re-parks on Sleep
    assert not fsm.done
    sleep_park = fsm.parked_on
    assert sleep_park is not None and sleep_park is not verify_request

    # a duplicate delivery of the SAME verify completion arrives late
    node.smm._awaiting_external += 1   # pair the handler's decrement
    node.smm._on_verify_done(fsm, manual.futures[0], verify_request)
    assert fsm.parked_on is sleep_park and not fsm.done


def test_rebuild_error_uses_whitelist_not_dynamic_import():
    """ADVICE r4 (low): checkpoint error payloads must reconstruct only
    whitelisted exception types — an arbitrary 'module:qualname' gadget
    (import side effects, arbitrary one-string-arg callables) degrades to
    FlowException instead of being imported and invoked."""
    from corda_tpu.flows.api import FlowException, FlowTimeoutException
    from corda_tpu.node.statemachine import _error_payload, _rebuild_error

    e = _rebuild_error(_error_payload(SignatureException("bad sig")))
    assert type(e) is SignatureException and str(e) == "bad sig"
    e = _rebuild_error(_error_payload(FlowTimeoutException("slow peer")))
    assert type(e) is FlowTimeoutException
    # legacy string payloads still work
    assert type(_rebuild_error("plain")) is FlowException

    for gadget in (["os.path:join", "x"], ["subprocess:Popen", "sleep 9"],
                   ["builtins:exec", "1+1"], ["no.such.module:X", "y"]):
        rebuilt = _rebuild_error(gadget)
        assert type(rebuilt) is FlowException, gadget


# ---------------------------------------------------------------------------
# VerifyMany against the Tpu service: the WAVE's size picks the route
# ---------------------------------------------------------------------------

class WaveFlow(FlowLogic):
    def __init__(self, stxs):
        self.stxs = stxs

    def call(self):
        from corda_tpu.flows.api import VerifyMany
        try:
            yield VerifyMany(tuple(self.stxs))
        except SignatureException as e:
            return f"caught:{e}"
        return "verified"


def _corrupt(stx):
    sig = stx.sigs[0]
    return stx.__class__(stx.tx_bits, (sig.__class__(
        sig.bytes[:-1] + bytes([sig.bytes[-1] ^ 1]), sig.by),))


def _wave(svcs, bad=()):
    stxs = [make_issue_stx(svcs, i) for i in range(5)]
    return [_corrupt(s) if i in bad else s for i, s in enumerate(stxs)]


def _spy_routes(batcher):
    """Thread names where the rows are queued, collected and host-verified,
    and the sizes of the batches a stubbed device was handed (host
    verdicts, no kernel)."""
    import threading
    seen = {"hold": [], "collect": [], "host_loop": [], "device_batches": []}

    def record(key, name):
        orig = getattr(batcher, name)

        def spy(*a, **k):
            seen[key].append(threading.current_thread().name)
            return orig(*a, **k)
        setattr(batcher, name, spy)

    record("hold", "hold_group")
    record("collect", "collect_group")
    record("host_loop", "_run_host")

    def device(bucket, items, reason="full", bctx=None):
        seen["device_batches"].append(len(items))
        batcher._mark_device(items)
        batcher._resolve(bucket, items, batcher._run_host(items), bctx)

    batcher._dispatch_device = device
    return seen


@pytest.mark.parametrize("bad,outcome", [((), "verified"), ((3, 1), 1)],
                         ids=["all_valid", "first_failure_in_order"])
def test_wave_at_the_crossover_is_one_bulk_burst_from_the_node_thread(
        bad, outcome):
    """At or over the crossover ``VerifyMany`` hands the wave to
    ``verify_wave``, which admits it as ONE ``submit_groups`` call in the
    bulk class on the node's thread and ONE completion task: no member is
    held, no member is a pool task of its own."""
    import threading
    network, node = make_network_node()
    svcs = seed_services(node)
    # 5 one-signature members against a crossover of 5; max_batch 5 makes
    # the whole wave ready at its cap: no deadline is waited for
    batcher = SignatureBatcher(host_crossover=5, max_batch=5)
    svc = TpuTransactionVerifierService(batcher=batcher)
    node.services.verifier_service = svc
    seen = _spy_routes(batcher)
    bursts, tasks = [], []
    submit_groups, pool_submit = batcher.submit_groups, svc._pool.submit

    def spy_groups(groups, ctxs=None, latency_class="bulk"):
        bursts.append((threading.current_thread().name, len(groups),
                       latency_class))
        return submit_groups(groups, ctxs, latency_class)

    def spy_pool(fn, *a, **k):
        tasks.append(fn.__name__)
        return pool_submit(fn, *a, **k)

    batcher.submit_groups, svc._pool.submit = spy_groups, spy_pool
    stxs = _wave(svcs, bad)
    try:
        with batcher._lock:     # re-entrant: the planner waits for the wave
            fsm = node.start_flow(WaveFlow(stxs))
            assert len(batcher._queues["ed25519"].bulk) == 5
            assert not batcher._queues["ed25519"].interactive
        assert bursts == [(threading.current_thread().name, 5, "bulk")]
        assert tasks == ["_complete_wave"]
        assert seen["hold"] == [] and seen["collect"] == []
        assert node.smm.awaiting_external == 1
        network.run_network()
        got = fsm.result_future.result(timeout=60)
        assert got == "verified" if outcome == "verified" else \
            stxs[outcome].id.prefix_chars() in got
        assert seen["device_batches"] == [5]
        # the stub's one host loop, on a batcher thread: no worker ran one
        assert len(seen["host_loop"]) == 1
        assert not seen["host_loop"][0].startswith("tpu-verifier")
        snap = batcher.metrics.snapshot()
        assert snap["SigBatcher.DeviceChecked"]["count"] == 5
        assert snap["SigBatcher.DeviceChecked.ed25519"]["count"] == 5
        assert "SigBatcher.HostInline" not in snap
        assert "SigBatcher.HostRouted" not in snap
        waves = svc.metrics.snapshot()
        assert waves["Verifier.WaveTx.bulk"]["count"] == 5
        assert "Verifier.WaveTx.held" not in waves
    finally:
        node.services.verifier_service.shutdown()


@pytest.mark.parametrize("bad,outcome", [((), "verified"), ((4, 2), 2)],
                         ids=["all_valid", "first_failure_in_order"])
def test_wave_under_the_crossover_goes_inline_member_by_member(bad, outcome):
    network, node = make_network_node()
    svcs = seed_services(node)
    batcher = SignatureBatcher(host_crossover=6)
    node.services.verifier_service = TpuTransactionVerifierService(
        batcher=batcher)
    seen = _spy_routes(batcher)
    stxs = _wave(svcs, bad)
    try:
        fsm = node.start_flow(WaveFlow(stxs))
        assert node.smm.awaiting_external == 1
        network.run_network()
        got = fsm.result_future.result(timeout=60)
        assert got == "verified" if outcome == "verified" else \
            stxs[outcome].id.prefix_chars() in got
        assert len(seen["hold"]) == 5 and len(seen["host_loop"]) == 5
        assert all(n.startswith("tpu-verifier")
                   for n in seen["collect"] + seen["host_loop"])
        assert seen["device_batches"] == []
        snap = batcher.metrics.snapshot()
        for meter in ("HostInline", "HostRouted", "Checked"):
            assert snap[f"SigBatcher.{meter}"]["count"] == 5
        assert snap["SigBatcher.InFlight"]["value"] == 0
        waves = node.services.verifier_service.metrics.snapshot()
        assert waves["Verifier.WaveTx.held"]["count"] == 5
        assert "Verifier.WaveTx.bulk" not in waves
    finally:
        node.services.verifier_service.shutdown()


def test_a_wave_reaches_only_a_service_that_takes_one():
    """ManualVerifierService has verify_signed and no verify_wave:
    VerifyMany must keep calling such a service member by member."""
    network, node = make_network_node()
    svcs = seed_services(node)
    manual = ManualVerifierService()
    node.services.verifier_service = manual
    fsm = node.start_flow(WaveFlow(_wave(svcs)))
    assert len(manual.futures) == 5
    for fut in manual.futures:
        fut.set_result(None)
    network.run_network()
    assert fsm.result_future.result(timeout=30) == "verified"


@pytest.mark.parametrize("flows,crossover,device_batches", [
    (8, 4, [8]),     # together at or over the crossover: one device batch
    (3, 4, []),      # together under it: each on the worker that serves it
], ids=["at_or_over", "under"])
def test_lone_verifies_suspended_together_are_judged_as_one_depth(
        flows, crossover, device_batches):
    """One-signature ``Verify`` flows suspended together share the
    batcher's queue, and the crossover is held against THAT depth, not
    against each transaction's own signature count (the lock is held while
    they start, so the depth is the same for whoever judges it first)."""
    network, node = make_network_node()
    svcs = seed_services(node)
    batcher = SignatureBatcher(host_crossover=crossover,
                               interactive_batch=flows)
    node.services.verifier_service = TpuTransactionVerifierService(
        batcher=batcher)
    seen = _spy_routes(batcher)
    try:
        with batcher._lock:
            fsms = [node.start_flow(VerifyFlow(make_issue_stx(svcs, i)))
                    for i in range(flows)]
            assert len(batcher._queues["ed25519"]) == flows
        network.run_network()
        assert [f.result_future.result(timeout=60) for f in fsms] \
            == ["verified"] * flows
        assert seen["device_batches"] == device_batches
        snap = batcher.metrics.snapshot()
        inline = 0 if device_batches else flows
        assert snap.get("SigBatcher.DeviceChecked", {"count": 0})["count"] \
            == flows - inline
        assert snap.get("SigBatcher.HostInline", {"count": 0})["count"] \
            == inline
        assert snap["SigBatcher.InFlight"]["value"] == 0
    finally:
        node.services.verifier_service.shutdown()


# ---------------------------------------------------------------------------
# The ORDERED VerifyMany: a walk's levels in one suspension
# ---------------------------------------------------------------------------

def make_move_stx(services, parents, i):
    """Spends every parent's output; owner and notary sign: two rows."""
    from corda_tpu.core.contracts import StateRef
    wtx = WireTransaction(
        inputs=tuple(StateRef(p.id, 0) for p in parents),
        outputs=(TransactionState(DummyState(i, (ALICE_KP.public,)), NOTARY),),
        commands=(Command(DummyContract.Move(), (ALICE_KP.public,)),),
        notary=NOTARY, must_sign=(ALICE_KP.public, NOTARY_KP.public))
    return services.sign_transaction(wtx, ALICE_KP.public, NOTARY_KP.public)


def _levels(svcs, width=5, bad=False):
    """``width`` issues, two moves that spend them between them, a move that
    spends both, and one more on top; nothing of it is in any store.
    ``bad``: the first move's owner signature is corrupted (member
    ``width`` of the order)."""
    issues = [make_issue_stx(svcs, 40 + i) for i in range(width)]
    moves = [make_move_stx(svcs, issues[:2], 50),
             make_move_stx(svcs, issues[2:], 51)]
    if bad:
        moves[0] = moves[0].__class__(moves[0].tx_bits, (
            _corrupt(moves[0]).sigs[0], moves[0].sigs[1]))
    join = make_move_stx(svcs, moves, 60)
    return (tuple(issues), tuple(moves), (join,),
            (make_move_stx(svcs, [join], 70),))


class LevelsFlow(FlowLogic):
    """Yields a walk's levels whole; what it caught, and then (``pause``)
    one more suspension, so that a restart has something to replay up to."""

    def __init__(self, levels, pause=False):
        self.levels = levels
        self.pause = pause

    def call(self):
        from corda_tpu.flows.api import Sleep, VerifyMany
        got = "verified"
        try:
            yield VerifyMany(levels=self.levels)
        except Exception as e:
            got = (type(e).__name__, e.verified)
        if self.pause:
            yield Sleep(3600)
        return got


def test_one_level_form_is_the_ordered_form_with_one_level():
    from corda_tpu.flows.api import VerifyMany
    a, b, c = object(), object(), object()
    wave = VerifyMany((a, b))
    assert wave.levels == ((a, b),) and wave.stxs == (a, b)
    walk = VerifyMany(levels=((a,), (b, c)), check_sufficient_signatures=False)
    assert walk.stxs == (a, b, c) and walk.levels == ((a,), (b, c))
    assert VerifyMany(()).stxs == () and not walk.check_sufficient_signatures


def test_levels_are_one_park_and_each_level_keeps_its_route():
    """Four levels in one ``VerifyMany``: ONE suspension and one pool task;
    the level at the crossover is one bulk burst (from the task's thread:
    the node's only parks), the levels under it are held and collected on
    that same thread, and each member's inputs resolve from the request."""
    import threading
    network, node = make_network_node()
    svcs = seed_services(node)
    batcher = SignatureBatcher(host_crossover=5, max_batch=5)
    svc = TpuTransactionVerifierService(batcher=batcher)
    node.services.verifier_service = svc
    seen = _spy_routes(batcher)
    bursts, tasks = [], []
    submit_groups, pool_submit = batcher.submit_groups, svc._pool.submit
    batcher.submit_groups = lambda groups, ctxs=None, latency_class="bulk": (
        bursts.append((threading.current_thread().name, len(groups),
                       latency_class)),
        submit_groups(groups, ctxs, latency_class))[1]
    svc._pool.submit = lambda fn, *a, **k: (
        tasks.append(fn.__name__), pool_submit(fn, *a, **k))[1]
    try:
        fsm = node.start_flow(LevelsFlow(_levels(svcs)))
        assert node.smm.awaiting_external == 1
        assert len(node.smm.checkpoints.get_all_checkpoints()) == 1
        network.run_network()
        assert fsm.result_future.result(timeout=60) == "verified"
        assert tasks == ["_verify_in_order"]
        ((task, n, klass),) = bursts
        assert task.startswith("tpu-verifier") and (n, klass) == (5, "bulk")
        assert seen["hold"] == seen["collect"] == [task] * 4
        assert seen["device_batches"] == [5]
        snap = batcher.metrics.snapshot()
        assert snap["SigBatcher.DeviceChecked"]["count"] == 5
        assert snap["SigBatcher.HostInline"]["count"] == 8
        assert snap["SigBatcher.HostRouted"]["count"] == 8
        assert snap["SigBatcher.InFlight"]["value"] == 0
        waves = svc.metrics.snapshot()
        assert waves["Verifier.WaveTx.bulk"]["count"] == 5
        assert waves["Verifier.WaveTx.held"]["count"] == 4
        assert node.services.storage.transactions == []   # nothing recorded
    finally:
        svc.shutdown()


class PrefixOnlyService(ManualVerifierService):
    """``verify_signed`` alone, each future resolved at once on the caller's
    thread with the transaction's own verdict."""

    def verify_signed(self, stx, services, check_sufficient_signatures=True):
        fut = super().verify_signed(stx, services)
        try:
            stx.verify(services,
                       check_sufficient_signatures=check_sufficient_signatures)
            fut.set_result(None)
        except Exception as e:
            fut.set_exception(e)
        return fut


def _backend(kind, network, node):
    from corda_tpu.verifier import InMemoryTransactionVerifierService
    if kind == "fallback":
        return None
    if kind == "member_by_member":
        return PrefixOnlyService()
    if kind == "in_memory":
        return InMemoryTransactionVerifierService()
    if kind == "tpu":
        return TpuTransactionVerifierService()
    svc = OutOfProcessTransactionVerifierService(node.messaging)
    VerifierWorker(network.bus.create_node("verifier-worker-1"),
                   str(node.info.address))
    return svc


BACKENDS = ["fallback", "member_by_member", "in_memory", "tpu",
            "out_of_process"]


@pytest.mark.parametrize("kind", BACKENDS)
@pytest.mark.parametrize("bad", [False, True], ids=["all_valid", "one_bad"])
def test_ordered_request_has_one_outcome_behind_every_backend(kind, bad):
    """Every service behind the seam is handed the members with the view
    over the walk, so each resolves what the store does not hold; a failure
    is the first in the order, and ``verified`` the members before it."""
    network, node = make_network_node()
    svcs = seed_services(node)
    svc = node.services.verifier_service = _backend(kind, network, node)
    network.run_network()       # a worker's handshake
    try:
        fsm = node.start_flow(LevelsFlow(_levels(svcs, bad=bad)))
        assert node.smm.awaiting_external == (0 if kind == "fallback" else 1)
        network.run_network()
        got = fsm.result_future.result(timeout=60)
    finally:
        if hasattr(svc, "shutdown"):
            svc.shutdown()
    if not bad:
        assert got == "verified"
    elif kind == "out_of_process":  # the worker's answer is a message
        assert got[1] == 5
    else:
        assert got == ("SignatureException", 5)


@pytest.mark.parametrize("kind", ["fallback", "by_hand"])
def test_failure_in_an_ordered_request_replays_with_its_type_and_count(kind):
    """The count rides in the response log beside the typed error: a flow
    that caught the failure and parked again is restored to the same
    ``(type, verified)``, whichever path logged it."""
    network, node = make_network_node()
    svcs = seed_services(node)
    levels = _levels(svcs, width=2, bad=True)
    if kind == "fallback":      # verified here, in the flow's own step
        fsm = node.start_flow(LevelsFlow(levels, pause=True))
    else:
        manual = node.services.verifier_service = ManualVerifierService()
        fsm = node.start_flow(LevelsFlow(levels, pause=True))
        assert len(manual.futures) == 6         # every member, handed over
        for i in (5, 3, 4, 0, 1):               # out of order, on purpose
            manual.futures[i].set_result(None)
        manual.futures[2].set_exception(SignatureException("flipped"))
        network.run_network()
    assert not fsm.result_future.done()         # asleep, past the failure
    (held,) = node.smm.checkpoints.get_all_checkpoints()
    (error,) = [value for what, value in held.response_log if what == "error"]
    assert error[0].endswith(":SignatureException") and error[2] == 2
    node2 = node.restart()
    node2.services.verifier_service = ManualVerifierService()
    node2.start()               # replays the failure, sleeps again
    assert node2.services.verifier_service.futures == []    # none asked anew
    (restored,) = node2.smm.flows.values()
    assert node2.smm.wake_timers(now=node2.smm.clock() + 4000) == 1
    assert restored.result_future.result(timeout=30) \
        == ("SignatureException", 2)
