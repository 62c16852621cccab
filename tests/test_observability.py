"""Observability subsystem: histogram percentile math, span tracer +
explicit context propagation across the batcher's threads and the
disabled (no-op) fast path."""
import json
import threading

import pytest

from corda_tpu.core.crypto import generate_keypair
from corda_tpu.core.crypto.schemes import ECDSA_SECP256K1_SHA256
from corda_tpu.core.crypto.signatures import Crypto
from corda_tpu.observability import (NOOP_SPAN, NOOP_TRACER, SpanRing,
                                     Tracer, disable_tracing, enable_tracing,
                                     get_tracer)
from corda_tpu.utils.metrics import Histogram, MetricRegistry
from corda_tpu.verifier.batcher import SignatureBatcher

KP = generate_keypair(ECDSA_SECP256K1_SHA256, entropy=b"\x61" * 32)
CONTENT = b"observability content"
SIG = Crypto.sign_with_key(KP, CONTENT).bytes


@pytest.fixture(autouse=True)
def _noop_after():
    yield
    disable_tracing()


# ---------------------------------------------------------------------------
# Histogram
# ---------------------------------------------------------------------------

def test_histogram_percentiles_within_bucket_resolution():
    h = Histogram()
    values = [0.001 * i for i in range(1, 101)]   # 1ms .. 100ms
    for v in values:
        h.update(v)
    # fixed log buckets: estimate within one quarter-decade (x1.78) of truth
    for q, want in ((0.50, 0.050), (0.90, 0.090), (0.99, 0.099)):
        got = h.quantile(q)
        assert want / 1.79 <= got <= want * 1.79, (q, got, want)
    assert h.quantile(1.0) <= h.max_value
    fields = h.snapshot_fields()
    assert fields["count"] == 100
    assert fields["max"] == pytest.approx(0.1)
    assert fields["mean"] == pytest.approx(sum(values) / 100)
    assert fields["p50"] <= fields["p90"] <= fields["p99"] <= fields["max"]


def test_histogram_empty_and_single_sample():
    h = Histogram()
    assert h.quantile(0.5) == 0.0
    assert h.snapshot_fields()["count"] == 0
    h.update(0.25)
    # one sample: every quantile clamps to the observed max exactly
    assert h.quantile(0.5) == 0.25
    assert h.quantile(0.99) == 0.25


def test_histogram_interpolates_within_bucket_boundaries():
    """Regression pin: quantile() used to snap to the bucket's UPPER edge,
    so 95 identical 2.0s samples reported p50 = 3.162 (the quarter-decade
    bound above 2.0) — a +58% tail overstatement at every bucket boundary.
    Linear interpolation inside the bucket keeps the estimate near the
    mass."""
    h = Histogram()
    for _ in range(95):
        h.update(2.0)
    for _ in range(5):
        h.update(1000.0)
    p50 = h.quantile(0.50)
    # 2.0 lives in bucket (1.778, 3.162]; rank 50 of the 95 samples there
    # interpolates to ~2.51 — strictly inside, never the 3.162 edge
    assert 1.778 < p50 < 3.0
    assert p50 == pytest.approx(2.507, rel=0.01)
    # the tail quantile still never exceeds the observed max
    assert 500.0 < h.quantile(0.99) <= 1000.0
    assert h.quantile(0.50) <= h.quantile(0.90) <= h.quantile(0.99)


def test_histogram_in_registry_snapshot_and_prometheus():
    from corda_tpu.tools.webserver import prometheus_text
    reg = MetricRegistry()
    reg.histogram("tx_verify_seconds").update(0.005)
    snap = reg.snapshot()
    assert snap["tx_verify_seconds"]["count"] == 1
    assert set(snap["tx_verify_seconds"]) == {
        "type", "count", "sum", "max", "mean", "p50", "p90", "p99",
        "buckets"}
    assert snap["tx_verify_seconds"]["type"] == "histogram"
    text = prometheus_text(snap)
    assert "corda_tpu_tx_verify_seconds_count 1" in text
    assert "corda_tpu_tx_verify_seconds_p99" in text
    with pytest.raises(TypeError):
        reg.counter("tx_verify_seconds")   # name/type collision stays typed


# ---------------------------------------------------------------------------
# Tracer + ring
# ---------------------------------------------------------------------------

def test_tracer_parenting_and_ring_query():
    tracer = Tracer(capacity=64)
    with tracer.span("root", kind="test") as root:
        with tracer.span("child", parent=root.context()) as child:
            child.set_tag("n", 3)
    spans = tracer.trace(root.trace_id)
    assert [s["name"] for s in spans] == ["child", "root"]  # finish order
    by_name = {s["name"]: s for s in spans}
    assert by_name["child"]["parent_id"] == root.span_id
    assert by_name["child"]["tags"] == {"n": 3}
    assert by_name["root"]["parent_id"] is None
    assert tracer.traces() == {root.trace_id: spans}
    # wire-tuple parents (the messaging form) attach to the same trace
    ctx = tracer.record("retro", parent=(root.trace_id, root.span_id),
                        start_s=1.0, duration_s=0.5)
    assert ctx.trace_id == root.trace_id
    assert len(tracer.trace(root.trace_id)) == 3


def test_span_ring_caps_and_exports(tmp_path):
    ring = SpanRing(capacity=4)
    for i in range(7):
        ring.record({"name": f"s{i}", "trace_id": "t", "span_id": str(i)})
    assert len(ring) == 4 and ring.dropped == 3
    assert [s["name"] for s in ring.snapshot()] == ["s3", "s4", "s5", "s6"]
    assert [s["name"] for s in ring.snapshot(limit=2)] == ["s5", "s6"]
    path = tmp_path / "spans.jsonl"
    assert ring.export_jsonl(str(path)) == 4
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert [s["name"] for s in lines] == ["s3", "s4", "s5", "s6"]


def test_span_ring_survives_concurrent_writers():
    """N threads hammering one ring: no exception, the ring holds exactly
    `capacity` spans, and drop accounting balances the total written."""
    ring = SpanRing(capacity=32)
    n_threads, per_thread = 8, 200

    def writer(t):
        for i in range(per_thread):
            ring.record({"name": f"w{t}-{i}", "trace_id": "t",
                         "span_id": f"{t}-{i}"})

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert len(ring) == 32
    assert ring.dropped == n_threads * per_thread - 32
    assert len(ring.snapshot()) == 32


def test_spans_dropped_surfaces_as_registry_gauge():
    """The ServiceHub monitoring registry exposes the ring's drop counter
    (Tracing.SpansDropped) so an overflowing flight recorder is visible on
    /metrics instead of silently losing history."""
    from corda_tpu.testing import MockNetwork
    tracer = enable_tracing(capacity=4)
    network = MockNetwork()
    node = network.create_node("O=Drops, L=Oslo, C=NO")
    network.start_nodes()
    for i in range(10):            # 10 spans into a 4-slot ring → 6 drops
        tracer.record(f"s{i}")
    snap = node.services.monitoring.snapshot()
    assert snap["Tracing.SpansDropped"]["value"] == 6
    assert snap["Tracing.SpansBuffered"]["value"] == 4
    disable_tracing()              # no-op tracer has no ring: gauges read 0
    snap = node.services.monitoring.snapshot()
    assert snap["Tracing.SpansDropped"]["value"] == 0
    assert snap["Tracing.SpansBuffered"]["value"] == 0


def test_error_inside_span_is_tagged():
    tracer = Tracer()
    with pytest.raises(ValueError):
        with tracer.span("boom"):
            raise ValueError("nope")
    (span,) = tracer.spans()
    assert span["tags"]["error"].startswith("ValueError")


# ---------------------------------------------------------------------------
# Disabled path (the default)
# ---------------------------------------------------------------------------

def test_disabled_tracing_is_inert_no_threads_no_metrics():
    assert get_tracer() is NOOP_TRACER
    before = threading.active_count()
    span = get_tracer().span("anything", parent=None, x=1)
    assert span is NOOP_SPAN and span.context() is None
    with span:
        span.set_tag("y", 2)
    assert get_tracer().record("retro") is None
    assert get_tracer().spans() == [] and get_tracer().traces() == {}
    # enabling installs NO background threads either — purely passive
    enable_tracing(capacity=16)
    assert threading.active_count() == before
    disable_tracing()
    assert get_tracer() is NOOP_TRACER


def test_disabled_tracing_batcher_adds_no_trace_metrics():
    """With the no-op tracer, the host verify path must not grow any
    trace-only artifacts: no spans anywhere, and the per-item enqueue
    stamps stay unset (near-free disabled path)."""
    batcher = SignatureBatcher(max_latency_s=0.01)
    try:
        assert batcher.submit(KP.public, SIG, CONTENT).result(timeout=120)
    finally:
        batcher.close()
    assert get_tracer().spans() == []
    snap = batcher.metrics.snapshot()
    # the stage histograms themselves still work (they're metrics, not
    # tracing): the host dispatch recorded a batch
    assert snap["verifier_batch_size"]["count"] == 1


# ---------------------------------------------------------------------------
# Propagation across the batcher's dispatcher/finisher threads
# ---------------------------------------------------------------------------

def test_trace_propagates_across_batcher_threads():
    tracer = enable_tracing()
    root = tracer.span("tx.verify", n_sigs=1)
    batcher = SignatureBatcher(max_latency_s=0.01)
    try:
        fut = batcher.submit(KP.public, SIG, CONTENT, ctx=root.context())
        assert fut.result(timeout=120)
    finally:
        batcher.close()
    root.finish()
    spans = tracer.trace(root.trace_id)
    names = {s["name"] for s in spans}
    # submit happened on this thread; flush + dispatch on the dispatcher
    # thread; resolve on whichever finished — one trace across all of them
    assert {"batcher.enqueue_wait", "batcher.flush", "batcher.dispatch",
            "batcher.resolve", "tx.verify"} <= names
    by_name = {s["name"]: s for s in spans}
    assert by_name["batcher.dispatch"]["tags"]["route"] == "host"
    assert by_name["batcher.flush"]["tags"]["batch_size"] == 1
    assert by_name["batcher.flush"]["tags"]["flush_reason"] in (
        "deadline", "stalled", "small_batch", "close")
    # every span carries the SAME trace id (no orphaned second trace)
    assert all(s["trace_id"] == root.trace_id for s in spans)


def test_batch_stage_histograms_populate():
    batcher = SignatureBatcher(max_latency_s=0.01)
    try:
        futs = batcher.submit_many(
            [(KP.public, SIG, CONTENT) for _ in range(5)])
        assert all(f.result(timeout=120) for f in futs)
    finally:
        batcher.close()
    snap = batcher.metrics.snapshot()
    assert snap["verifier_batch_size"]["count"] >= 1
    assert snap["verifier_batch_size"]["max"] >= 1
    assert snap["verifier_dispatch_seconds"]["count"] >= 1
    assert snap["verifier_finish_seconds"]["count"] >= 1
    # host-only run: no device prep happened, so the stage has no samples
    assert not snap.get("verifier_prep_seconds", {}).get("count")
