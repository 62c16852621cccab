"""/traces endpoint + the end-to-end acceptance trace: one transaction
verified through TransactionVerifierService produces ONE trace whose spans
cover submit → batch flush → dispatch → resolve, retrievable over HTTP."""
import json
import urllib.error
import urllib.request

import pytest

import corda_tpu.finance  # noqa: F401
from corda_tpu.core.contracts import Command, TransactionState
from corda_tpu.core.crypto import generate_keypair
from corda_tpu.core.identity import Party
from corda_tpu.core.transactions import WireTransaction
from corda_tpu.node.rpc import CordaRPCOps
from corda_tpu.observability import disable_tracing, enable_tracing
from corda_tpu.testing import (DUMMY_NOTARY_NAME, DummyContract, DummyState,
                               MockNetwork, MockServices)
from corda_tpu.verifier import TpuTransactionVerifierService

NOTARY_KP = generate_keypair(entropy=b"\x20" * 32)
NOTARY = Party(DUMMY_NOTARY_NAME, NOTARY_KP.public)
ALICE_KP = generate_keypair(entropy=b"\x21" * 32)


@pytest.fixture(autouse=True)
def _noop_after():
    yield
    disable_tracing()


def _make_stx(services):
    wtx = WireTransaction(
        outputs=(TransactionState(DummyState(7, (ALICE_KP.public,)), NOTARY),),
        commands=(Command(DummyContract.Create(), (ALICE_KP.public,)),),
        notary=NOTARY, must_sign=(ALICE_KP.public,))
    return services.sign_transaction(wtx, ALICE_KP.public)


def _verify_one_stx():
    services = MockServices(key_pairs=[NOTARY_KP, ALICE_KP], parties=[NOTARY])
    svc = TpuTransactionVerifierService()
    try:
        assert svc.verify_signed(_make_stx(services),
                                 services).result(timeout=120) is None
    finally:
        svc.shutdown()


def test_single_tx_verify_produces_one_end_to_end_trace():
    tracer = enable_tracing()
    _verify_one_stx()
    traces = tracer.traces()
    # ONE trace: every span of the pipeline shares the root's trace id
    assert len(traces) == 1
    (spans,) = traces.values()
    names = {s["name"] for s in spans}
    assert {"tx.verify", "verifier.submit", "batcher.enqueue_wait",
            "batcher.flush", "batcher.dispatch", "batcher.resolve",
            "verifier.resolve", "verifier.run"} <= names
    roots = [s for s in spans if s["name"] == "tx.verify"]
    assert len(roots) == 1 and roots[0]["parent_id"] is None
    assert roots[0]["tags"]["n_sigs"] == 1
    dispatch = next(s for s in spans if s["name"] == "batcher.dispatch")
    assert dispatch["tags"]["route"] in ("host", "device")
    # parent links all resolve within the same trace
    ids = {s["span_id"] for s in spans}
    for s in spans:
        assert s["parent_id"] is None or s["parent_id"] in ids


@pytest.fixture
def web():
    network = MockNetwork()
    network.create_notary_node()
    alice = network.create_node("O=Alice, L=Madrid, C=ES")
    network.start_nodes()
    from corda_tpu.tools.webserver import NodeWebServer
    ops = CordaRPCOps(alice.services, alice.smm)
    server = NodeWebServer(ops, pump=network.run_network).start()
    yield server
    server.stop()


def _get_json(server, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}{path}", timeout=10) as r:
        return json.loads(r.read())


def test_traces_endpoint_disabled_then_live(web):
    server = web
    # no-op tracer: well-formed empty answer, never an error
    out = _get_json(server, "/traces")
    assert out == {"enabled": False, "traces": {}}
    tracer = enable_tracing()
    _verify_one_stx()
    out = _get_json(server, "/traces")
    assert out["enabled"] is True and len(out["traces"]) == 1
    (trace_id,) = out["traces"]
    names = {s["name"] for s in out["traces"][trace_id]}
    assert {"tx.verify", "batcher.flush", "batcher.dispatch",
            "batcher.resolve"} <= names
    # filtered + limited view
    one = _get_json(server, f"/traces?trace_id={trace_id}&limit=2")
    assert one["trace_id"] == trace_id and len(one["spans"]) == 2
    assert _get_json(server, "/traces?trace_id=feedfacedeadbeef")["spans"] == []
    # JSONL export view: one JSON object per line, same span set
    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/traces?format=jsonl",
            timeout=10) as r:
        assert r.headers["Content-Type"].startswith("application/x-ndjson")
        lines = [json.loads(l) for l in r.read().decode().splitlines()]
    assert {s["name"] for s in lines} == {s["name"] for s in tracer.spans()}


def test_metrics_endpoint_exposes_verifier_histograms():
    from corda_tpu.tools.webserver import prometheus_text
    from corda_tpu.utils.metrics import MetricRegistry
    reg = MetricRegistry()
    services = MockServices(key_pairs=[NOTARY_KP, ALICE_KP], parties=[NOTARY])
    svc = TpuTransactionVerifierService(metrics=reg)
    try:
        assert svc.verify_signed(_make_stx(services),
                                 services).result(timeout=120) is None
    finally:
        svc.shutdown()
    text = prometheus_text(reg.snapshot())
    for metric in ("verifier_batch_size", "verifier_dispatch_seconds",
                   "tx_verify_seconds"):
        for q in ("p50", "p90", "p99"):
            assert f"corda_tpu_{metric}_{q}" in text, (metric, q)


def test_traces_endpoint_stitches_cross_process_fleet_trace(web):
    """An out-of-process verification produces ONE trace whose spans come
    from BOTH sides of the process seam — the node's verifier.oop_submit
    and the worker's worker.* child spans — retrievable over /traces."""
    import time
    from corda_tpu.network.inmemory import InMemoryMessagingNetwork
    from corda_tpu.verifier.fleet import make_sig_checks
    from corda_tpu.verifier.out_of_process import (
        OutOfProcessTransactionVerifierService, VerifierWorker)

    enable_tracing()
    bus = InMemoryMessagingNetwork()
    svc = OutOfProcessTransactionVerifierService(bus.create_node("node"))
    worker = VerifierWorker(bus.create_node("w1"), "node")
    bus.run_network()
    fut = svc.verify_signatures(make_sig_checks(4))
    deadline = time.monotonic() + 60
    while not fut.done():
        bus.run_network()
        time.sleep(0.005)
        assert time.monotonic() < deadline, "verification did not resolve"
    assert fut.result(timeout=1) is None

    out = _get_json(web, "/traces")
    assert out["enabled"] is True
    stitched = [spans for spans in out["traces"].values()
                if {"verifier.oop_submit", "worker.device_dispatch"}
                <= {s["name"] for s in spans}]
    assert stitched, "no stitched cross-process trace on /traces"
    (spans,) = stitched
    submit = next(s for s in spans if s["name"] == "verifier.oop_submit")
    dispatch = next(s for s in spans if s["name"] == "worker.device_dispatch")
    assert dispatch["parent_id"] == submit["span_id"]
    assert dispatch["tags"]["worker"] == "w1"
    worker.stop()


def test_live_fleet_serves_federated_metrics_and_request_timelines():
    """A live two-worker fleet behind NodeWebServer: /metrics carries a
    worker-labeled federated family (the workers ship their snapshots on
    load reports) and /debug/requests holds the requests' timelines."""
    import time
    from corda_tpu.tools.webserver import NodeWebServer
    from corda_tpu.verifier.fleet import InProcessFleet, make_sig_checks

    class FleetOps:
        def __init__(self, fleet):
            self.fleet = fleet

        def metrics_snapshot(self):
            return self.fleet.metrics.snapshot()

        def request_timelines(self, limit=None):
            return self.fleet.service.request_log.snapshot(limit=limit)

    fleet = InProcessFleet(2, use_device=False)
    server = NodeWebServer(FleetOps(fleet)).start()
    try:
        checks = make_sig_checks(16)
        for fut in [fleet.verify_signatures(checks) for _ in range(8)]:
            fut.result(timeout=120)
        deadline = time.monotonic() + 30   # the next load reports arrive
        while True:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/metrics",
                    timeout=10) as r:
                text = r.read().decode()
            shipped = any(
                line.startswith("corda_tpu_sigbatcher_checked_count{")
                and 'worker="' in line for line in text.splitlines())
            if shipped or time.monotonic() > deadline:
                break
            time.sleep(0.05)
        assert shipped, "no worker's SigBatcher.Checked on /metrics"
        timelines = _get_json(server, "/debug/requests")["requests"]
        assert len(timelines) >= 8
        assert all(tl[0]["event"] == "submitted" for tl in timelines.values())
    finally:
        server.stop()
        fleet.close()


def test_traces_endpoint_min_duration_filter(web):
    """?min_duration_ms= keeps only traces whose longest span clears the
    threshold — the tail-forensics entry point (find the slow ones)."""
    server = web
    tracer = enable_tracing()
    slow = tracer.record("flow.run", duration_s=2.0)
    tracer.record("tx.verify", parent=slow, duration_s=0.5)
    tracer.record("flow.run", duration_s=0.001)   # separate fast trace
    out = _get_json(server, "/traces")
    assert len(out["traces"]) == 2
    out = _get_json(server, "/traces?min_duration_ms=1000")
    assert len(out["traces"]) == 1
    (spans,) = out["traces"].values()
    assert {s["name"] for s in spans} == {"flow.run", "tx.verify"}
    # threshold above every trace: empty, not an error
    assert _get_json(server, "/traces?min_duration_ms=60000")["traces"] == {}
    # composes with trace_id (filtered single-trace view unaffected)
    assert _get_json(
        server,
        f"/traces?trace_id={slow.trace_id}&min_duration_ms=60000")["spans"]
    # malformed value is a 400, not a 500
    try:
        _get_json(server, "/traces?min_duration_ms=soon")
        assert False, "expected HTTP 400"
    except urllib.error.HTTPError as e:
        assert e.code == 400


def test_debug_critpath_endpoint(web):
    """/debug/critpath returns the blame decomposition of live traces:
    per-class vectors that sum to the class e2e, and a top-K list of
    slowest transactions with annotated blocking chains."""
    server = web
    # tracing off: well-formed empty report
    out = _get_json(server, "/debug/critpath")
    assert out["traces"] == 0 and out["top"] == []
    tracer = enable_tracing()
    # synthetic commit path: flow.run with a verify child and a notary
    # wait — the decomposition must cover all 4s
    root = tracer.record("flow.run", start_s=100.0, duration_s=4.0,
                         flow_type="corda_tpu.finance.cash.CashPaymentFlow")
    tracer.record("tx.verify", parent=root, start_s=100.5, duration_s=1.0)
    tracer.record("wait.await_future", parent=root, start_s=101.5,
                  duration_s=2.0, wait_kind="notary.commit")
    out = _get_json(server, "/debug/critpath?top_k=3")
    assert out["traces"] == 1
    assert out["per_class"]["pay"]["n"] == 1
    blame = out["per_class"]["pay"]["blame_p50"]
    assert abs(sum(blame.values()) - 4000.0) < 1.0   # conservation
    assert blame["verify"] == pytest.approx(1000.0)
    assert blame["notary.batch_wait"] == pytest.approx(2000.0)
    (top,) = out["top"]
    assert top["e2e_ms"] == pytest.approx(4000.0)
    kinds = [s["wait_kind"] for s in top["segments"]]
    assert "notary.commit" in kinds
    # bad top_k is a 400
    try:
        _get_json(server, "/debug/critpath?top_k=many")
        assert False, "expected HTTP 400"
    except urllib.error.HTTPError as e:
        assert e.code == 400
