"""A device-routed batch's life in spans: ``batcher.submit`` on the caller's
thread, ``batcher.queue_wait`` and ``batcher.pool_wait`` under the flush,
the Ed25519 prep's phases and ``batcher.launch`` under the dispatch; and with
tracing off, no stamp and no clock that was not written or read before.

No EC kernel is compiled: the device seam is a host stand-in
(``_start_ed25519``), or the jitted kernel alone is (``_verify_kernel_split``
behind the real prep and the real ``KernelProfiler.call``)."""
import os
import threading
import time

import numpy as np
import pytest

from corda_tpu.core.crypto import generate_keypair
from corda_tpu.core.crypto.schemes import EDDSA_ED25519_SHA512
from corda_tpu.core.crypto.signatures import Crypto
from corda_tpu.observability import tracing
from corda_tpu.observability.tracing import (
    NOOP_SPAN, Tracer, disable_tracing, set_tracer)
from corda_tpu.ops import ed25519 as ed_ops
from corda_tpu.ops import scalarprep as sp
from corda_tpu.utils.metrics import MetricRegistry
from corda_tpu.verifier import batcher as batcher_mod
from corda_tpu.verifier.batcher import SignatureBatcher

KP = generate_keypair(EDDSA_ED25519_SHA512, entropy=b"\x39" * 32)
CONTENT = b"a batch's life"
ROW = (KP.public, Crypto.sign_with_key(KP, CONTENT).bytes, CONTENT)


@pytest.fixture
def tracer():
    t = Tracer()
    set_tracer(t)
    try:
        yield t
    finally:
        disable_tracing()


def _host_stand_in(batcher, seen=None):
    """All-valid verdicts in place of prep + kernel; keeps what it was
    handed as the dispatch span."""
    def start(items, dspan=None):
        if seen is not None:
            seen.append(dspan)
        return None, lambda pending: [True] * len(items)
    batcher._start_ed25519 = start


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def test_submit_queue_wait_and_pool_wait_of_one_batch(tracer):
    b = SignatureBatcher(metrics=MetricRegistry(), host_crossover=0,
                         max_batch=8, bucket_ladder=(8,))
    handed = []
    _host_stand_in(b, handed)
    try:
        with tracer.span("caller") as caller:
            fut = b.submit_group([ROW] * 8, ctx=caller.context())
        assert fut.result(timeout=30) == [True] * 8
    finally:
        b.close()
    spans = tracer.spans()
    (submit,) = _named(spans, "batcher.submit")
    (flush,) = _named(spans, "batcher.flush")
    (queue,) = _named(spans, "batcher.queue_wait")
    (pool,) = _named(spans, "batcher.pool_wait")
    (dispatch,) = _named(spans, "batcher.dispatch")
    (root,) = _named(spans, "caller")
    # the submission: the caller's thread, the first group's context
    assert submit["parent_id"] == root["span_id"]
    assert submit["tags"] == {"rows": 8, "groups": 1}
    assert submit["thread"] == threading.current_thread().name
    assert submit["cpu_s"] is not None
    # the two waits: children of the flush, end to end, in that order
    assert queue["parent_id"] == pool["parent_id"] == flush["span_id"]
    assert queue["tags"] == {"bucket": "ed25519", "rows": 8,
                             "flush_reason": "max_batch"}
    assert pool["tags"] == {"bucket": "ed25519", "rows": 8}
    assert queue["cpu_s"] is None and pool["cpu_s"] is None
    cut = queue["start_s"] + queue["duration_s"]
    assert pool["start_s"] == pytest.approx(cut, abs=1e-6)
    assert flush["start_s"] == pytest.approx(
        pool["start_s"] + pool["duration_s"], abs=1e-6)
    # the rows joined the queue inside the submission
    assert submit["start_s"] <= queue["start_s"] \
        <= submit["start_s"] + submit["duration_s"] + 1e-4
    # the stand-in was handed the dispatch span itself, which ran on a CPU
    assert handed[0].span_id == dispatch["span_id"]
    assert dispatch["cpu_s"] is not None
    assert dispatch["tags"]["route"] == "device"


def test_queue_wait_starts_at_the_oldest_rows_enqueue(tracer):
    """Two submissions share a batch: its queue wait runs from the FIRST
    one's enqueue. The planner is held off while both join the queue."""
    b = SignatureBatcher(metrics=MetricRegistry(), host_crossover=0,
                         max_batch=8, bucket_ladder=(8,), max_latency_s=5.0)
    _host_stand_in(b)
    try:
        with b._lock:        # re-entrant: the planner cannot cut meanwhile
            first = b.submit_group([ROW] * 3)
            time.sleep(0.15)
            second = b.submit_many([ROW] * 5)
            held = b._queues["ed25519"].bulk
            stamps = sorted({p.t_enq for p in held})
            assert len(held) == 8 and len(stamps) == 2   # one a submission
        assert first.result(timeout=30) == [True] * 3
        assert [f.result(timeout=30) for f in second] == [True] * 5
    finally:
        b.close()
    spans = tracer.spans()
    (queue,) = _named(spans, "batcher.queue_wait")
    assert queue["start_s"] == stamps[0]
    assert queue["duration_s"] >= 0.15
    assert queue["tags"]["rows"] == 8
    submits = _named(spans, "batcher.submit")
    assert [s["tags"] for s in submits] == [{"rows": 3, "groups": 1},
                                            {"rows": 5, "groups": 0}]
    # no caller's context: each submission is a trace of its own
    assert submits[0]["parent_id"] is None and submits[1]["parent_id"] is None
    assert submits[0]["trace_id"] != submits[1]["trace_id"]


def test_a_host_routed_plan_has_its_waits_and_an_inline_flush_has_none(
        tracer):
    b = SignatureBatcher(metrics=MetricRegistry(), use_device=False)
    try:
        assert b.submit_group([ROW] * 2).result(timeout=30) == [True] * 2
        assert b.collect_group(b.hold_group([ROW])) == [True]
    finally:
        b.close()
    spans = tracer.spans()
    flushes = {s["span_id"]: s for s in _named(spans, "batcher.flush")}
    assert len(flushes) == 2
    (queue,) = _named(spans, "batcher.queue_wait")
    (pool,) = _named(spans, "batcher.pool_wait")
    planned = flushes[queue["parent_id"]]
    assert pool["parent_id"] == planned["span_id"]
    assert planned["tags"]["route"] == "host" \
        and "inline" not in planned["tags"]
    # hold_group is no submission of the planner's
    assert len(_named(spans, "batcher.submit")) == 1


def _kernel_stand_in(monkeypatch):
    """The real prep and the real ``KernelProfiler.call`` around a host
    function where the jitted kernel would be."""
    def kernel(bb_idx, a_digits, rows, r_packed, w):
        return np.ones(rows.shape[0], dtype=bool)

    from corda_tpu.observability import profiling
    monkeypatch.setattr(profiling, "_PROFILER", profiling.KernelProfiler())
    monkeypatch.setattr(ed_ops, "_verify_kernel_split", kernel)
    monkeypatch.setattr(ed_ops, "b_table_device", lambda w, shift=0: ())
    monkeypatch.setattr(ed_ops, "split_field_products", lambda rows, w: 0)


@pytest.mark.parametrize("library", ["loaded", "absent", "stale"])
def test_the_prep_phases_and_the_launch_are_the_dispatchs_children(
        library, tracer, monkeypatch):
    """With libscalarmath.so the word prep's one native call runs and the
    rows are marked on ``Ed25519WordsPrep``; with none, or one of another
    ``sm_version`` offered to the loader, the pure-Python form runs in
    silence and they are marked on ``Ed25519ItemsPrep``
    (``ed25519_words_prep_share`` then reads under 100): the spans and the
    verdicts are the same."""
    if library == "stale":
        real = next(p for p in sp._CANDIDATES if os.path.exists(p))
        assert sp._load(candidates=[real],
                        expected=sp.SM_VERSION - 1) is None
    if library != "loaded":
        monkeypatch.setattr(sp, "_LIB", None)
    marked, unmarked = ("Ed25519WordsPrep", "Ed25519ItemsPrep") \
        if library == "loaded" else ("Ed25519ItemsPrep", "Ed25519WordsPrep")
    _kernel_stand_in(monkeypatch)
    b = SignatureBatcher(metrics=MetricRegistry(), host_crossover=0,
                         max_batch=8, bucket_ladder=(8,))
    try:
        bad = (KP.public, ROW[1][:-1] + bytes([ROW[1][-1] ^ 0x80]), CONTENT)
        got = b.submit_group([ROW] * 5 + [bad]).result(timeout=30)
    finally:
        b.close()
    # s >= L is refused by the prep itself; the stand-in accepts the rest
    assert got == [True] * 5 + [False]
    snap = b.metrics.snapshot()
    assert snap[f"SigBatcher.{marked}"]["count"] == 6        # by rows
    assert f"SigBatcher.{unmarked}" not in snap
    assert "SigBatcher.BatchFailure" not in snap
    spans = tracer.spans()
    (dispatch,) = _named(spans, "batcher.dispatch")
    children = [s for s in spans if s["parent_id"] == dispatch["span_id"]]
    assert [s["name"] for s in children] == [
        f"ed25519.prep.{p}"
        for p in ("items", "sig", "keys", "digest", "scalars", "handover")] \
        + ["batcher.launch"]
    items, *phases, launch = children
    assert items["tags"] == {"bucket": "ed25519", "rows": 6}
    for s in phases:        # the kernels pad 6 rows to their 8-row bucket
        assert s["tags"] == {"bucket": "ed25519", "rows": 8}
    # a fresh flight recorder has not seen the shape: its first call is
    # what ``KernelProfiler.call`` books as a compile
    assert launch["tags"] == {"bucket": "ed25519", "rows": 6, "capacity": 8,
                              "compiled": True}
    end = dispatch["start_s"] + dispatch["duration_s"]
    at = dispatch["start_s"]
    for s in children:
        assert s["cpu_s"] is not None
        assert s["start_s"] >= at - 1e-4
        at = s["start_s"] + s["duration_s"]
    assert at <= end + 1e-4
    # the device wait and the resolve hang under the flush, after the launch
    (wait,) = _named(spans, "batcher.device_wait")
    (resolve,) = _named(spans, "batcher.resolve")
    assert wait["parent_id"] == resolve["parent_id"] == dispatch["parent_id"]
    assert wait["start_s"] >= at - 1e-4


def test_the_spans_name_all_but_a_twentieth_of_a_dispatch(tracer,
                                                          monkeypatch):
    """``dispatch_unnamed_ms_p50``'s bound, at a batch large enough to have
    a duration: the seven children cover a device dispatch but for under 5%
    of it (the best of five batches: a thread descheduled between two spans
    is the machine's, not the code's)."""
    _kernel_stand_in(monkeypatch)
    b = SignatureBatcher(metrics=MetricRegistry(), host_crossover=0,
                         max_batch=4096, bucket_ladder=(4096,))
    try:
        for _ in range(5):
            assert all(b.submit_group([ROW] * 4096).result(timeout=60))
    finally:
        b.close()
    spans = tracer.spans()
    shares = []
    for dispatch in _named(spans, "batcher.dispatch"):
        named = sum(s["duration_s"] for s in spans
                    if s["parent_id"] == dispatch["span_id"])
        shares.append(1.0 - named / dispatch["duration_s"])
    assert len(shares) == 5
    assert min(shares) < 0.05, shares


def test_with_tracing_off_no_stamp_is_written_and_no_wall_clock_read(
        monkeypatch):
    """``t_enq`` stays 0.0, a plan carries no cut stamp, every span site
    gets the no-op, and neither the batcher nor the tracer reads the wall
    clock or a thread's CPU clock."""
    class Clock:
        """``time`` with the clocks tracing alone reads taken out."""
        def __getattr__(self, name):
            if name in ("time", "thread_time"):
                raise AssertionError(f"time.{name}() read with tracing off")
            return getattr(time, name)

    monkeypatch.setattr(batcher_mod, "_time", Clock())
    monkeypatch.setattr(tracing, "time", Clock())
    assert not tracing.get_tracer().enabled
    b = SignatureBatcher(metrics=MetricRegistry(), host_crossover=0,
                         max_batch=8, bucket_ladder=(8,), max_latency_s=5.0)
    handed, plans = [], []
    _host_stand_in(b, handed)
    flush = b._submit_flush
    b._submit_flush = lambda *plan: (plans.append(plan), flush(*plan))[1]
    try:
        with b._lock:
            fut = b.submit_group([ROW] * 8)
            rows = list(b._queues["ed25519"].bulk)
        assert fut.result(timeout=30) == [True] * 8
        assert b.submit(*ROW).result(timeout=30) is True
    finally:
        b.close()
    assert len(rows) == 8
    assert all(p.t_enq == 0.0 and p.ctx is None for p in rows)
    assert [plan[-1] for plan in plans] == [None, None]     # t_cut absent
    assert handed == [NOOP_SPAN, NOOP_SPAN]
    assert "SigBatcher.BatchFailure" not in b.metrics.snapshot()
