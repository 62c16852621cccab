"""A span that says how long its thread RAN (``Tracer.span(..., cpu=True)``):
``cpu_s`` beside ``duration_s``, from the opening thread's CPU clock. For a
span that only computes, ``duration_s - cpu_s`` is the time its thread was
runnable and not running: in this program, the wait for the interpreter
lock."""
import threading
import time

from corda_tpu.observability import tracing
from corda_tpu.observability.tracing import (
    NOOP_SPAN, NOOP_TRACER, Tracer, make_span_dict)


def _one(tracer, name):
    (span,) = [s for s in tracer.spans() if s["name"] == name]
    return span


def test_a_sleeping_span_reads_cpu_near_zero():
    tracer = Tracer()
    with tracer.span("asleep", cpu=True):
        time.sleep(0.2)
    span = _one(tracer, "asleep")
    assert span["duration_s"] >= 0.2
    assert 0.0 <= span["cpu_s"] < 0.05


def test_a_spinning_span_reads_cpu_near_its_duration():
    tracer = Tracer()
    with tracer.span("spin", cpu=True):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 0.2:
            pass
    span = _one(tracer, "spin")
    # (wide: the test machine's other workers take the core now and then)
    assert 0.4 * span["duration_s"] <= span["cpu_s"] \
        <= 1.05 * span["duration_s"]


def test_two_spinning_threads_under_one_lock_each_ran_about_half():
    """What the batcher's sites read: the thread's share of the lock."""
    tracer = Tracer()

    def spin():
        with tracer.span("contended", cpu=True):
            t0, x = time.perf_counter(), 0
            while time.perf_counter() - t0 < 0.6:
                x += 1

    threads = [threading.Thread(target=spin) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    spans = [s for s in tracer.spans() if s["name"] == "contended"]
    assert len(spans) == 2
    off = sum(s["duration_s"] - s["cpu_s"] for s in spans)
    wall = sum(s["duration_s"] for s in spans)
    assert 0.25 < off / wall < 0.9


def test_cpu_is_off_unless_asked_and_every_record_carries_the_key():
    tracer = Tracer()
    with tracer.span("plain", bucket="x"):
        pass
    assert _one(tracer, "plain")["cpu_s"] is None
    tracer.record("retro", start_s=1.0, duration_s=2.0)
    assert _one(tracer, "retro")["cpu_s"] is None
    # a span finished twice keeps its first reading
    span = tracer.span("twice", cpu=True)
    span.finish()
    first = span.cpu_s
    span.finish()
    assert span.cpu_s == first and len(tracer.spans()) == 3


def test_the_noop_takes_the_argument_and_reads_no_clock(monkeypatch):
    class NoClock:
        def __getattr__(self, name):
            raise AssertionError(f"tracing read time.{name} with tracing off")

    monkeypatch.setattr(tracing, "time", NoClock())
    span = NOOP_TRACER.span("anything", parent=None, cpu=True, rows=3)
    assert span is NOOP_SPAN
    with span as inner:
        assert inner.set_tag("rows", 3) is NOOP_SPAN
    assert span.context() is None


def test_ingest_tolerates_a_dict_without_cpu_s():
    tracer = Tracer()
    worker_span = make_span_dict("worker.decode", ("t1", "s1"), 5.0, 0.25)
    assert "cpu_s" not in worker_span      # an older worker's span has none
    tracer.ingest(worker_span)
    tracer.ingest({"trace_id": "t1", "span_id": "s9", "cpu_s": 0.125,
                   "duration_s": 0.5})
    by_id = {s["span_id"]: s for s in tracer.spans()}
    assert by_id[worker_span["span_id"]]["cpu_s"] is None
    assert by_id["s9"]["cpu_s"] == 0.125


def test_the_noop_span_as_a_parent_starts_a_fresh_trace():
    """Tracing came on between a caller's span and its child's: the child
    must not raise on the no-op parent it was handed."""
    tracer = Tracer()
    with tracer.span("child", parent=NOOP_SPAN):
        pass
    span = _one(tracer, "child")
    assert span["parent_id"] is None and span["trace_id"]
