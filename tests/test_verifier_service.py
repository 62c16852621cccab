"""Verifier-service tests: async SPI, device-batched signature checking.

Reference analogs: InMemoryTransactionVerifierService behavior, the
OutOfProcess service's metrics wiring (OutOfProcessTransactionVerifierService.kt:33-45),
and VerifierTests.kt's "all transactions verify / invalid one fails" cases.
"""
import threading

import pytest

from corda_tpu.core.contracts import (Command, StateRef, TransactionState)
from corda_tpu.core.crypto import generate_keypair
from corda_tpu.core.crypto.schemes import (ECDSA_SECP256K1_SHA256,
                                           EDDSA_ED25519_SHA512)
from corda_tpu.core.crypto.signatures import Crypto, SignatureException
from corda_tpu.core.identity import Party
from corda_tpu.core.transactions import (SignaturesMissingException,
                                         SignedTransaction, WireTransaction)
from corda_tpu.testing import (DUMMY_NOTARY_NAME, DummyContract, DummyState,
                               MockServices)
from corda_tpu.verifier import (SignatureBatcher,
                                InMemoryTransactionVerifierService,
                                TpuTransactionVerifierService,
                                make_verifier_service)

NOTARY_KP = generate_keypair(entropy=b"\x20" * 32)
NOTARY = Party(DUMMY_NOTARY_NAME, NOTARY_KP.public)
ALICE_KP = generate_keypair(entropy=b"\x21" * 32)
ALICE_K1_KP = generate_keypair(ECDSA_SECP256K1_SHA256, entropy=b"\x22" * 32)


def make_issue_stx(services, owner_kp=ALICE_KP):
    wtx = WireTransaction(
        outputs=(TransactionState(DummyState(7, (owner_kp.public,)), NOTARY),),
        commands=(Command(DummyContract.Create(), (owner_kp.public,)),),
        notary=NOTARY, must_sign=(owner_kp.public,))
    return services.sign_transaction(wtx, owner_kp.public)


@pytest.fixture
def services():
    return MockServices(key_pairs=[NOTARY_KP, ALICE_KP, ALICE_K1_KP],
                        parties=[NOTARY])


def test_in_memory_service_verifies(services):
    stx = make_issue_stx(services)
    svc = InMemoryTransactionVerifierService()
    fut = svc.verify(stx.to_ledger_transaction(services))
    assert fut.result(timeout=30) is None
    snap = svc.metrics.snapshot()
    assert snap["Verification.Success"]["count"] == 1
    svc.shutdown()


def test_in_memory_service_propagates_failure(services):
    from corda_tpu.core.contracts import SignersMissing
    wtx = WireTransaction(
        outputs=(TransactionState(DummyState(7, (ALICE_KP.public,)), NOTARY),),
        commands=(Command(DummyContract.Create(), (ALICE_KP.public,)),),
        notary=NOTARY, must_sign=())  # required signer missing
    stx = services.sign_transaction(wtx, ALICE_KP.public)
    svc = InMemoryTransactionVerifierService()
    fut = svc.verify(stx.to_ledger_transaction(services))
    with pytest.raises(SignersMissing):
        fut.result(timeout=30)
    assert svc.metrics.snapshot()["Verification.Failure"]["count"] == 1
    svc.shutdown()


def test_signature_batcher_mixed_schemes(services):
    batcher = SignatureBatcher(max_latency_s=0.01)
    content = b"batched content"
    futures, want = [], []
    for i in range(6):
        kp = [ALICE_KP, ALICE_K1_KP, NOTARY_KP][i % 3]
        sig = Crypto.sign_with_key(kp, content)
        sig_bytes = sig.bytes if i % 4 != 3 else sig.bytes[:-2] + b"\x00\x00"
        futures.append(batcher.submit(kp.public, sig_bytes, content))
        want.append(Crypto.is_valid(kp.public, sig_bytes, content))
    got = [f.result(timeout=120) for f in futures]
    assert got == want
    assert False in got and True in got
    assert batcher.metrics.snapshot()["SigBatcher.Checked"]["count"] == 6
    assert batcher.metrics.snapshot()["SigBatcher.InFlight"]["value"] == 0
    batcher.close()


def test_tpu_service_full_path(services):
    svc = TpuTransactionVerifierService()
    stx = make_issue_stx(services)
    assert svc.verify_signed(stx, services).result(timeout=120) is None

    # corrupted signature → SignatureException from the device verdict
    bad_sig = stx.sigs[0].__class__(
        stx.sigs[0].bytes[:-1] + bytes([stx.sigs[0].bytes[-1] ^ 1]),
        stx.sigs[0].by)
    bad_stx = SignedTransaction(stx.tx_bits, (bad_sig,))
    with pytest.raises(SignatureException):
        svc.verify_signed(bad_stx, services).result(timeout=120)

    # signature by the wrong key → coverage failure
    k1_stx_wtx = stx.tx
    other = SignedTransaction.of(
        k1_stx_wtx, [services.sign(k1_stx_wtx.id.bytes, ALICE_K1_KP.public)])
    with pytest.raises(SignaturesMissingException):
        svc.verify_signed(other, services).result(timeout=120)
    svc.shutdown()


def test_verify_signed_submits_one_group_per_tx(services):
    """Acceptance pin: the TPU service path asks the batcher for ONE group
    (one future) per transaction's signature set, never per-signature
    submit_many futures (~25µs of Future allocation each)."""
    svc = TpuTransactionVerifierService()
    calls = []
    orig = svc.batcher.hold_group

    def spy(checks, ctx=None, **kw):
        calls.append(len(checks))
        return orig(checks, ctx=ctx, **kw)

    def reject(*a, **k):
        raise AssertionError("verify_signed must not use submit_many")

    svc.batcher.hold_group = spy
    svc.batcher.submit_many = reject
    try:
        stx = make_issue_stx(services)
        assert svc.verify_signed(stx, services).result(timeout=120) is None
        assert calls == [len(stx.sigs)]
    finally:
        svc.shutdown()


def _spy_threads(obj, name):
    """Wrap ``obj.<name>``; returns the list of thread names it ran on."""
    seen, orig = [], getattr(obj, name)

    def spy(*a, **k):
        seen.append(threading.current_thread().name)
        return orig(*a, **k)

    setattr(obj, name, spy)
    return seen


def _corrupted(stx):
    sig = stx.sigs[0]
    return SignedTransaction(stx.tx_bits, (sig.__class__(
        sig.bytes[:-1] + bytes([sig.bytes[-1] ^ 1]), sig.by),))


def test_host_routed_verify_crosses_one_thread(services):
    """A lone transaction under the crossover is verified, signatures and
    contract rules, on the ``tpu-verifier`` worker that serves it: neither
    the planner nor the prep pool sees the group, and the caller's thread
    runs none of it."""
    svc = TpuTransactionVerifierService()
    b = svc.batcher
    seen = _spy_threads(b, "_run_host")
    held_on = _spy_threads(b, "hold_group")
    b._submit_flush = lambda *a, **k: pytest.fail("planner cut a plan")
    try:
        stx = make_issue_stx(services)
        assert svc.verify_signed(stx, services).result(timeout=30) is None
        assert held_on == [threading.current_thread().name]
        with pytest.raises(SignatureException):
            svc.verify_signed(_corrupted(stx), services).result(timeout=30)
        assert len(seen) == 2
        assert all(name.startswith("tpu-verifier") for name in seen)
        assert b._prep_pool is None
        snap = svc.metrics.snapshot()
        for meter in ("HostRouted", "Checked", "HostInline"):
            assert snap[f"SigBatcher.{meter}"]["count"] == 2
        assert snap["SigBatcher.InFlight"]["value"] == 0
        assert snap["Verification.Success"]["count"] == 1
        assert snap["Verification.Failure"]["count"] == 1
    finally:
        svc.shutdown()


def test_worker_waits_for_the_queue_when_the_depth_is_the_devices(services):
    """At or over the crossover the rows stay queued for the planner (here
    a crossover of 1 and a stubbed device): the worker takes nothing back
    and reads the planner's verdicts, valid and corrupted, as before."""
    b = SignatureBatcher(host_crossover=1)
    batches = []

    def device(bucket, items, reason="full", bctx=None):
        batches.append(len(items))
        b._mark_device(items)
        b._resolve(bucket, items, b._run_host(items), bctx)

    b._dispatch_device = device
    svc = TpuTransactionVerifierService(batcher=b)
    try:
        stx = make_issue_stx(services)
        assert svc.verify_signed(stx, services).result(timeout=30) is None
        with pytest.raises(SignatureException):
            svc.verify_signed(_corrupted(stx), services).result(timeout=30)
        assert batches == [1, 1]
        snap = b.metrics.snapshot()
        assert snap["SigBatcher.DeviceChecked"]["count"] == 2
        assert "SigBatcher.HostInline" not in snap
        assert snap["SigBatcher.InFlight"]["value"] == 0
    finally:
        svc.shutdown()


@pytest.mark.parametrize("entry", ["lone", "wave_under", "wave_at_crossover"])
def test_verify_signed_on_closed_batcher_returns_failed_future(services,
                                                               entry):
    """Span-leak fix: if the batcher rejects the group (closed), the
    caller must get a FAILED FUTURE (verify_signed's contract is async, and
    verify_wave's: one failed future a member) and the root span must still
    be finished, not leaked. The rows are refused on the caller's thread,
    whatever their wave's size: the futures come back already failed."""
    from corda_tpu.observability import disable_tracing, enable_tracing
    tracer = enable_tracing()
    svc = TpuTransactionVerifierService(
        batcher=SignatureBatcher(host_crossover=3))
    try:
        stx = make_issue_stx(services)
        svc.batcher.close()
        if entry == "lone":
            futs, root = [svc.verify_signed(stx, services)], "tx.verify"
        else:
            n = 2 if entry == "wave_under" else 3
            futs, root = svc.verify_wave([stx] * n, services), "verifier.wave"
            assert len(futs) == n
        for fut in futs:
            assert fut.done()
            with pytest.raises(RuntimeError, match="closed"):
                fut.result(timeout=5)
        # an unfinished span never reaches the ring: its presence IS the
        # proof that the root's finish() ran on the failure path
        names = [s["name"] for s in tracer.spans()]
        assert root in names
        if entry != "lone":
            (wave,) = [s for s in tracer.spans()
                       if s["name"] == "verifier.wave"]
            assert wave["tags"]["admitted"] == (
                "held" if entry == "wave_under" else "bulk")
    finally:
        disable_tracing()
        svc.shutdown()


def _stub_device(b):
    """Host verdicts behind the device route: no kernel is compiled."""
    batches = []

    def device(bucket, items, reason="full", bctx=None):
        batches.append((bucket, len(items)))
        b._mark_device(items)
        b._resolve(bucket, items, b._run_host(items), bctx)

    b._dispatch_device = device
    return batches


def _wave_of(services, n=4):
    """``n`` distinct one-signature issues, every other one secp256k1."""
    stxs = []
    for i in range(n):
        kp = ALICE_K1_KP if i % 2 else ALICE_KP
        wtx = WireTransaction(
            outputs=(TransactionState(DummyState(100 + i, (kp.public,)),
                                      NOTARY),),
            commands=(Command(DummyContract.Create(), (kp.public,)),),
            notary=NOTARY, must_sign=(kp.public,))
        stxs.append(services.sign_transaction(wtx, kp.public))
    return stxs


def test_a_wave_over_the_crossover_is_one_burst_and_members_fail_alone(
        services):
    """ONE ``submit_groups`` call in the bulk class and ONE completion task
    a wave, on the caller's thread; a bad signature in member 3 and a
    missing signer in member 2 leave the other four valid, each member answered
    with its own outcome and type; the spans and meters appear."""
    from corda_tpu.observability import disable_tracing, enable_tracing
    b = SignatureBatcher(host_crossover=2)
    batches = _stub_device(b)
    svc = TpuTransactionVerifierService(batcher=b)
    bursts, tasks = [], []
    submit_groups, pool_submit = b.submit_groups, svc._pool.submit

    def spy_groups(groups, ctxs=None, latency_class="bulk"):
        bursts.append((threading.current_thread().name, len(groups),
                       latency_class))
        return submit_groups(groups, ctxs, latency_class)

    def spy_pool(fn, *a, **k):
        tasks.append(fn.__name__)
        return pool_submit(fn, *a, **k)

    b.submit_groups, svc._pool.submit = spy_groups, spy_pool
    b.hold_group = lambda *a, **k: pytest.fail("a member was held")
    stxs = _wave_of(services, 6)
    stxs[2] = _corrupted(stxs[2])
    stxs[1] = SignedTransaction.of(
        stxs[1].tx, [services.sign(stxs[1].id.bytes, ALICE_KP.public)])
    tracer = enable_tracing()
    try:
        futs = svc.verify_wave(stxs, services)
        got = [f.exception(timeout=30) for f in futs]
    finally:
        disable_tracing()
        svc.shutdown()
    assert bursts == [(threading.current_thread().name, 6, "bulk")]
    assert tasks == ["_complete_wave"]
    assert [got[i] for i in (0, 3, 4, 5)] == [None] * 4
    assert type(got[1]) is SignaturesMissingException
    assert type(got[2]) is SignatureException
    assert stxs[2].id.prefix_chars() in str(got[2])
    # member 2's stand-in signature is Ed25519: four rows to two
    assert sorted(batches) == [("ed25519", 4), ("secp256k1", 2)]
    snap = svc.metrics.snapshot()
    assert snap["Verifier.WaveTx.bulk"]["count"] == 6
    assert "Verifier.WaveTx.held" not in snap
    assert snap["Verification.Success"]["count"] == 4
    assert snap["Verification.Failure"]["count"] == 2
    assert snap["Verification.InFlight"]["value"] == 0
    assert snap["tx_verify_seconds"]["count"] == 6
    # coverage ran for the five members whose signatures all verified
    assert snap["Verifier.RequiredKeys"]["count"] == 5
    assert snap["Verifier.CompositeRequired"]["count"] == 0
    device = b.metrics.snapshot()
    assert device["SigBatcher.DeviceChecked"]["count"] == 6
    assert device["SigBatcher.DeviceChecked.ed25519"]["count"] == 4
    assert device["SigBatcher.DeviceChecked.secp256k1"]["count"] == 2
    spans = {s["name"]: s for s in tracer.spans()}
    wave = spans["verifier.wave"]
    assert wave["tags"] == {"n_tx": 6, "n_sigs": 6, "admitted": "bulk",
                            "failed": 2}
    for child in ("submit", "verdicts", "coverage", "rules"):
        span = spans[f"verifier.wave.{child}"]
        assert span["parent_id"] == wave["span_id"], child
        assert (span["cpu_s"] is not None) == (child != "verdicts"), child
    assert spans["verifier.wave.coverage"]["tags"]["n_tx"] == 5
    assert spans["verifier.wave.rules"]["tags"]["n_tx"] == 4
    # the batcher's own spans hang under the wave, beside its passes
    assert spans["batcher.submit"]["parent_id"] == wave["span_id"]
    assert spans["batcher.flush"]["trace_id"] == wave["trace_id"]
    assert "tx.verify" not in spans


def test_a_wave_under_the_crossover_takes_the_path_a_member_took(services):
    """Under the crossover every member is held and collected on the worker
    that serves it, as before: ``SigBatcher.HostInline`` moves, the planner
    cuts nothing, and the wave's span closes with its last member."""
    from corda_tpu.observability import disable_tracing, enable_tracing
    b = SignatureBatcher(host_crossover=5)
    b._submit_flush = lambda *a, **k: pytest.fail("planner cut a plan")
    b.submit_groups = lambda *a, **k: pytest.fail("a burst under the "
                                                  "crossover")
    svc = TpuTransactionVerifierService(batcher=b)
    held = _spy_threads(b, "hold_group")
    stxs = _wave_of(services)
    stxs[2] = _corrupted(stxs[2])
    tracer = enable_tracing()
    try:
        futs = svc.verify_wave(stxs, services)
        got = [f.exception(timeout=30) for f in futs]
    finally:
        disable_tracing()
        svc.shutdown()
    assert [type(e) for e in got] == [type(None), type(None),
                                      SignatureException, type(None)]
    assert held == [threading.current_thread().name] * 4
    snap = svc.metrics.snapshot()
    assert snap["Verifier.WaveTx.held"]["count"] == 4
    assert "Verifier.WaveTx.bulk" not in snap
    for meter in ("HostInline", "HostRouted", "Checked"):
        assert b.metrics.snapshot()[f"SigBatcher.{meter}"]["count"] == 4
    spans = tracer.spans()
    (wave,) = [s for s in spans if s["name"] == "verifier.wave"]
    assert wave["tags"] == {"n_tx": 4, "n_sigs": 4, "admitted": "held"}
    roots = [s for s in spans if s["name"] == "tx.verify"]
    assert len(roots) == 4
    assert wave["start_s"] + wave["duration_s"] >= max(
        r["start_s"] + r["duration_s"] for r in roots) - 1e-3


def test_a_host_only_batcher_keeps_every_wave_with_its_members(services):
    """``use_device=False`` (and ``route_interactive_host``): the rows of a
    held member go to the host queue, so a wave of any size stays held."""
    b = SignatureBatcher(use_device=False, host_crossover=1)
    assert not b.wave_is_the_planners([ALICE_KP.public] * 500, 500)
    forced = SignatureBatcher(host_crossover=1)
    try:
        assert forced.wave_is_the_planners([ALICE_KP.public], 1)
        forced.route_interactive_host(True)
        assert not forced.wave_is_the_planners([ALICE_KP.public], 1)
    finally:
        forced.close()
    svc = TpuTransactionVerifierService(batcher=b)
    try:
        futs = svc.verify_wave(_wave_of(services), services)
        assert [f.exception(timeout=30) for f in futs] == [None] * 4
        assert svc.metrics.snapshot()["Verifier.WaveTx.held"]["count"] == 4
    finally:
        svc.shutdown()


def test_the_coverage_pass_meters_composite_keys_and_their_leaves(services):
    """A 2-of-3 owner signed by two leaves is covered, by one it is not;
    ``Verifier.CompositeRequired`` counts the composite required keys and
    ``Verifier.CompositeLeafVisits`` the leaves the walks looked up."""
    from corda_tpu.core.crypto.composite import CompositeKey
    leaves = [generate_keypair(entropy=bytes([0x30 + i]) * 32)
              for i in range(3)]
    owner = CompositeKey.Builder().add_keys(
        *(kp.public for kp in leaves)).build(2)
    wtx = WireTransaction(
        outputs=(TransactionState(DummyState(9, (owner,)), NOTARY),),
        commands=(Command(DummyContract.Create(), (owner,)),),
        notary=NOTARY, must_sign=(owner,))

    def signed_by(*kps):
        return SignedTransaction.of(
            wtx, [Crypto.sign_with_key(kp, wtx.id.bytes) for kp in kps])

    order = [c.node for c in owner.children]     # the walk's own order
    first, second = (next(kp for kp in leaves if kp.public == k)
                     for k in order[:2])
    b = SignatureBatcher(host_crossover=2, max_batch=4)
    _stub_device(b)
    svc = TpuTransactionVerifierService(batcher=b)
    try:
        futs = svc.verify_wave([signed_by(first, second), signed_by(first)],
                               services)
        got = [f.exception(timeout=30) for f in futs]
    finally:
        svc.shutdown()
    assert got[0] is None and type(got[1]) is SignaturesMissingException
    snap = svc.metrics.snapshot()
    assert snap["Verifier.RequiredKeys"]["count"] == 2
    assert snap["Verifier.CompositeRequired"]["count"] == 2
    # covered: the walk stops at the second leaf; not covered: all three
    assert snap["Verifier.CompositeLeafVisits"]["count"] == 2 + 3


def test_inline_flush_spans_hang_under_the_callers_tx_verify(services):
    from corda_tpu.observability import disable_tracing, enable_tracing
    tracer = enable_tracing()
    svc = TpuTransactionVerifierService()
    try:
        stx = make_issue_stx(services)
        assert svc.verify_signed(stx, services).result(timeout=30) is None
    finally:
        disable_tracing()
        svc.shutdown()
    by_name = {s["name"]: s for s in tracer.spans()}
    root, flush = by_name["tx.verify"], by_name["batcher.flush"]
    assert flush["tags"]["route"] == "host"
    assert flush["tags"]["inline"] is True
    assert flush["tags"]["flush_reason"] == "small_batch"
    assert flush["parent_id"] == root["span_id"]
    assert by_name["batcher.enqueue_wait"]["parent_id"] == root["span_id"]
    for name in ("batcher.dispatch", "batcher.resolve"):
        assert by_name[name]["parent_id"] == flush["span_id"]
    assert by_name["batcher.dispatch"]["tags"]["route"] == "host"
    threads = {by_name[n]["thread"] for n in (
        "batcher.flush", "batcher.dispatch", "batcher.resolve",
        "verifier.run", "verifier.resolve")}
    assert len(threads) == 1 and threads.pop().startswith("tpu-verifier")
    assert all(s["trace_id"] == root["trace_id"] for s in tracer.spans())


def test_inline_route_records_nothing_with_tracing_off(services):
    from corda_tpu.observability import get_tracer
    svc = TpuTransactionVerifierService()
    svc.batcher._trace_flush = lambda *a, **k: pytest.fail(
        "a flush span with the no-op tracer")
    stamped = []
    stamp = svc.batcher._stamp_trace

    def spy_stamp(pendings, ctx):
        stamped.append(ctx)
        return stamp(pendings, ctx)

    svc.batcher._stamp_trace = spy_stamp
    try:
        stx = make_issue_stx(services)
        assert svc.verify_signed(stx, services).result(timeout=30) is None
        assert svc.metrics.snapshot()["SigBatcher.HostInline"]["count"] == 1
    finally:
        svc.shutdown()
    assert stamped == [None]
    assert get_tracer().spans() == []


def test_make_verifier_service_seam():
    assert isinstance(make_verifier_service("InMemory"),
                      InMemoryTransactionVerifierService)
    svc = make_verifier_service("Tpu")
    assert isinstance(svc, TpuTransactionVerifierService)
    svc.shutdown()
    with pytest.raises(ValueError):
        make_verifier_service("Bogus")


def test_flows_route_verification_through_the_service_seam():
    """VERDICT r2: flows call hub.verify_transaction — with a TPU backend
    installed, a normal payment's signature checks ride the node's device
    batcher (the service seam composed with the node, not just bare
    kernels)."""
    import corda_tpu.finance  # noqa: F401
    from corda_tpu.core.contracts.amount import Amount, USD
    from corda_tpu.finance import CashIssueFlow, CashPaymentFlow
    from corda_tpu.testing import MockNetwork

    network = MockNetwork()
    notary = network.create_notary_node()
    alice = network.create_node("O=Alice, L=London, C=GB")
    bob = network.create_node("O=Bob, L=Paris, C=FR")
    network.start_nodes()
    batchers = {}
    for node in (notary, alice, bob):
        batcher = SignatureBatcher(host_crossover=0, max_latency_s=0.01)
        batchers[node] = batcher
        node.services.verifier_service = TpuTransactionVerifierService(
            batcher=batcher)
    try:
        fsm = alice.start_flow(CashIssueFlow(
            Amount(900, USD), b"\x01", alice.party, notary.party))
        network.run_network()
        fsm.result_future.result(timeout=5)
        fsm = alice.start_flow(CashPaymentFlow(Amount(400, USD), bob.party))
        deadline = __import__("time").monotonic() + 120
        while not fsm.result_future.done():
            network.run_network()
            __import__("time").sleep(0.01)
            assert __import__("time").monotonic() < deadline
        fsm.result_future.result(timeout=5)
        # bob's NotifyTransactionHandler verified the broadcast through HIS
        # device batcher (payment inputs -> his node resolves and verifies)
        snap = batchers[bob].metrics.snapshot()
        assert snap.get("SigBatcher.DeviceChecked", {}).get("count", 0) > 0
        assert [s.state.data.amount.quantity
                for s in bob.services.vault.unconsumed_states()] == [400]
    finally:
        for b in batchers.values():
            b.close()


# ---------------------------------------------------------------------------
# verify_levels: a walk's levels, whole and in order, in ONE task
# ---------------------------------------------------------------------------

def _issue(services, magic, kp=ALICE_KP):
    wtx = WireTransaction(
        outputs=(TransactionState(DummyState(magic, (kp.public,)), NOTARY),),
        commands=(Command(DummyContract.Create(), (kp.public,)),),
        notary=NOTARY, must_sign=(kp.public,))
    return services.sign_transaction(wtx, kp.public)


def _spend(services, parents, magic, kp=ALICE_KP):
    """A move of every parent's output into one new state, signed by the
    owner and the notary: two signature rows."""
    wtx = WireTransaction(
        inputs=tuple(StateRef(p.id, 0) for p in parents),
        outputs=(TransactionState(DummyState(magic, (kp.public,)), NOTARY),),
        commands=(Command(DummyContract.Move(), (kp.public,)),),
        notary=NOTARY, must_sign=(kp.public, NOTARY_KP.public))
    return services.sign_transaction(wtx, kp.public, NOTARY_KP.public)


def _graph(services, width):
    """Three levels, none of them recorded anywhere: ``width`` issues, two
    moves that spend them between them, one move that spends both."""
    issues = [_issue(services, 200 + i) for i in range(width)]
    half = width // 2
    moves = [_spend(services, issues[:half], 300),
             _spend(services, issues[half:], 301)]
    return [issues, moves, [_spend(services, moves, 400)]]


def _spy_levels(svc):
    """Where a request's bursts, holds and pool tasks happen."""
    b = svc.batcher
    seen = {"bursts": [], "tasks": [], "holds": _spy_threads(b, "hold_group"),
            "collects": _spy_threads(b, "collect_group")}
    submit_groups, pool_submit = b.submit_groups, svc._pool.submit

    def spy_groups(groups, ctxs=None, latency_class="bulk"):
        seen["bursts"].append((threading.current_thread().name, len(groups),
                               latency_class))
        return submit_groups(groups, ctxs, latency_class)

    def spy_pool(fn, *a, **k):
        seen["tasks"].append(fn.__name__)
        return pool_submit(fn, *a, **k)

    b.submit_groups, svc._pool.submit = spy_groups, spy_pool
    return seen


def test_levels_run_as_one_task_and_each_level_keeps_its_own_route(services):
    """Three levels in one request: ONE pool task; the level of five rows at
    the crossover is one bulk burst from that task's thread, the levels of
    four rows and two under it are held and collected on it, member after
    member; a member's inputs resolve from the request's own transactions."""
    from corda_tpu.node.services import ResolvedFromWalk
    from corda_tpu.observability import disable_tracing, enable_tracing
    b = SignatureBatcher(host_crossover=5, max_batch=5)
    batches = _stub_device(b)
    svc = TpuTransactionVerifierService(batcher=b)
    seen = _spy_levels(svc)
    levels = _graph(services, 5)
    walk = [stx for level in levels for stx in level]
    tracer = enable_tracing()
    try:
        got = svc.verify_levels(
            levels, ResolvedFromWalk(services, walk)).result(timeout=30)
    finally:
        disable_tracing()
        svc.shutdown()
    assert got == (8, None)
    assert seen["tasks"] == ["_verify_in_order"]
    (task,) = {name for name, _n, _c in seen["bursts"]}
    assert task.startswith("tpu-verifier")
    assert seen["bursts"] == [(task, 5, "bulk")]
    assert seen["holds"] == seen["collects"] == [task] * 3
    assert batches == [("ed25519", 5)]
    snap = svc.metrics.snapshot()
    assert snap["Verifier.WaveTx.bulk"]["count"] == 5
    assert snap["Verifier.WaveTx.held"]["count"] == 3
    assert snap["Verification.Success"]["count"] == 8
    assert snap["Verification.InFlight"]["value"] == 0
    rows = b.metrics.snapshot()
    assert rows["SigBatcher.DeviceChecked"]["count"] == 5
    assert rows["SigBatcher.HostInline"]["count"] == 6
    assert rows["SigBatcher.HostRouted"]["count"] == 6
    spans = tracer.spans()
    (whole,) = [s for s in spans if s["name"] == "verifier.levels"]
    assert whole["tags"] == {"levels": 3, "n_tx": 8, "verified": 8}
    assert whole["thread"] == task
    waves = [s for s in spans if s["name"] == "verifier.wave"]
    assert [w["tags"]["admitted"] for w in sorted(
        waves, key=lambda s: s["start_s"])] == ["bulk", "held", "held"]
    members = [s for s in spans if s["name"] == "tx.verify"]
    assert len(members) == 3        # a bulk level's members leave none
    assert all(s["parent_id"] == whole["span_id"] for s in waves + members)


def test_members_of_a_walk_do_not_resolve_from_a_store_that_lacks_them(
        services):
    """The view is what lets a level spend what an earlier level made: the
    same request against the bare services fails at the first move, with
    the issues before it counted as passed."""
    from corda_tpu.core.contracts.exceptions import (
        TransactionResolutionException)
    svc = TpuTransactionVerifierService()
    try:
        verified, error = svc.verify_levels(
            _graph(services, 2), services).result(timeout=30)
    finally:
        svc.shutdown()
    assert verified == 2 and type(error) is TransactionResolutionException


@pytest.mark.parametrize("bad,verified,holds", [
    ("held_level", 5, 2), ("bulk_level", 1, 0)])
def test_levels_stop_at_the_first_member_that_fails_in_order(
        services, bad, verified, holds):
    """Nothing after the first failure is vouched for: the levels behind it
    are never admitted, and the count is of the members before it. A level
    judges all its members (they do not depend on one another), held or
    bulk; the first of them to fail in order is the one."""
    from corda_tpu.node.services import ResolvedFromWalk
    b = SignatureBatcher(host_crossover=5, max_batch=5)
    _stub_device(b)
    svc = TpuTransactionVerifierService(batcher=b)
    levels = _graph(services, 5)
    if bad == "held_level":
        levels[1][0] = _corrupted(levels[1][0])
    else:
        levels[0][1] = _corrupted(levels[0][1])
        levels[0][3] = _corrupted(levels[0][3])
    failing = levels[1][0] if bad == "held_level" else levels[0][1]
    seen = _spy_levels(svc)
    walk = [stx for level in levels for stx in level]
    try:
        got, error = svc.verify_levels(
            levels, ResolvedFromWalk(services, walk)).result(timeout=30)
    finally:
        svc.shutdown()
    assert got == verified and type(error) is SignatureException
    assert failing.id.prefix_chars() in str(error)
    assert len(seen["bursts"]) == 1 and len(seen["holds"]) == holds
    assert seen["tasks"] == ["_verify_in_order"]


@pytest.mark.parametrize("entry", ["closed_batcher_one_level",
                                   "closed_batcher_levels",
                                   "shut_down_pool_levels"])
def test_verify_levels_resolves_its_future_and_never_fails_it(services,
                                                              entry):
    """The scheduler reads ``(verified, error)`` off the future on its own
    thread: a closed batcher or a shut-down pool comes back as a count of 0
    and the error, not as a raise."""
    svc = TpuTransactionVerifierService()
    levels = _graph(services, 2)
    try:
        if entry == "shut_down_pool_levels":
            svc._pool.shutdown(wait=True)
        else:
            svc.batcher.close()
        fut = svc.verify_levels(
            levels[:1] if entry == "closed_batcher_one_level" else levels,
            services)
        verified, error = fut.result(timeout=30)
    finally:
        svc.shutdown()
    assert verified == 0 and type(error) is RuntimeError
