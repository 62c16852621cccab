"""Continuous-batching pipeline tests (PR 6): shape-bucket compile
stability, latency classes, bulk admission control, and the staging-buffer
lease discipline.

The device seam is stubbed at ``_start_ed25519`` (the same seam the
breaker chaos tests pin) so these run in tier-1 without paying an XLA
compile: the stub routes a shape-faithful padded array through
``KernelProfiler.call`` — the profiler's novel-signature fallback then
counts a "compile" exactly when the batcher hands the kernel a shape it
has not seen, which is the property the bucket ladder exists to bound.
"""
import threading
import time

import numpy as np
import pytest

from corda_tpu.core.crypto import generate_keypair
from corda_tpu.core.crypto.schemes import EDDSA_ED25519_SHA512
from corda_tpu.core.crypto.signatures import Crypto
from corda_tpu.observability.profiling import (
    KernelProfiler, get_profiler, set_profiler)
from corda_tpu.ops import field as F
from corda_tpu.ops.staging import StagingPool
from corda_tpu.testing.faults import FaultRule, inject
from corda_tpu.utils.metrics import MetricRegistry
from corda_tpu.verifier.batcher import BULK, INTERACTIVE, SignatureBatcher

KP = generate_keypair(EDDSA_ED25519_SHA512, entropy=b"\x42" * 32)
CONTENT = b"continuous batching content"
SIG = Crypto.sign_with_key(KP, CONTENT).bytes
TRIPLE = (KP.public, SIG, CONTENT)


# -- bucket ladder ----------------------------------------------------------

def test_pow2_ladder_rungs():
    assert SignatureBatcher._pow2_ladder(256, 2048) == (256, 512, 1024, 2048)
    # a non-pow2 cap rides along as the one extra megabatch shape
    assert SignatureBatcher._pow2_ladder(256, 3000) == (
        256, 512, 1024, 2048, 3000)
    # cap below the floor collapses to a single rung
    assert SignatureBatcher._pow2_ladder(256, 128) == (128,)


def test_ladder_cut_prefers_largest_fitting_rung():
    b = SignatureBatcher(metrics=MetricRegistry(), use_device=False,
                         bucket_ladder=(8, 16, 32, 64), max_batch=64)
    try:
        assert b._ladder_cut("ed25519", 70) == 64
        assert b._ladder_cut("ed25519", 33) == 32
        assert b._ladder_cut("ed25519", 8) == 8
        # sub-floor tails dispatch at raw depth (the kernels pad them)
        assert b._ladder_cut("ed25519", 5) == 5
    finally:
        b.close()


@pytest.mark.parametrize("depth,cut,padded", [
    (100, 100, 128),        # a sub-floor tail: raw depth, the kernels' pad
    (300, 256, 256),        # the rung takes over half: as before
    (511, 256, 256),
    (512, 512, 8192),       # the rung would take half or less: the WHOLE
    (2600, 2600, 8192),     # depth, one flush padded to the next rung up
    (5400, 5400, 8192),
    (8192, 8192, 8192),
    (9000, 8192, 8192),     # over the top rung: a full bucket first
])
def test_a_sparse_ladder_does_not_mince_a_partial_bucket(depth, cut, padded):
    """Under ``[256, 8192]`` (the deployments' ladder) a partial bucket of
    5,400 rows left as 21 flushes of 256, each a prep's lock waits and an
    in-flight slot: it leaves as ONE, padded to 8,192, a shape the ladder
    names. A power-of-two ladder cuts as it did."""
    b = SignatureBatcher(metrics=MetricRegistry(), use_device=False,
                         bucket_ladder=(256, 8192), max_batch=8192)
    dense = SignatureBatcher(metrics=MetricRegistry(), use_device=False,
                             max_batch=8192)
    try:
        assert b._ladder_cut("ed25519", depth) == cut
        assert b._padded_rows("ed25519", cut) == padded
        fits = max([r for r in dense._default_ladder if r <= depth] or [0])
        assert dense._ladder_cut("ed25519", depth) == (fits or depth)
        assert dense._padded_rows("ed25519", fits or depth) \
            == max(128, fits)
    finally:
        b.close()
        dense.close()


def test_a_whole_partial_bucket_reaches_the_kernels_at_its_rung(monkeypatch):
    """The planner cuts 600 queued rows whole, the prep is told the rung
    (8,192) as its capacity, and the flush is metered at that shape."""
    from corda_tpu.core.crypto import generate_keypair
    from corda_tpu.core.crypto.signatures import Crypto
    from corda_tpu.ops import ed25519 as ed_ops
    kp = generate_keypair(entropy=b"\x51" * 32)
    row = (kp.public, Crypto.sign_with_key(kp, b"m" * 32).bytes, b"m" * 32)
    seen = []

    def words(keys, sigs, msgs, trace_parent=None, capacity=None):
        seen.append((len(keys), capacity))
        return ("stub", len(keys))

    monkeypatch.setattr(ed_ops, "verify_batch_async_words", words)
    monkeypatch.setattr(ed_ops, "finish_batch",
                        lambda pending: [True] * pending[1])
    metrics = MetricRegistry()
    b = SignatureBatcher(metrics=metrics, bucket_ladder=(256, 8192),
                         max_batch=8192)
    try:
        assert b.submit_group([row] * 600).result(timeout=30) == [True] * 600
    finally:
        b.close()
    assert seen == [(600, 8192)]
    snap = metrics.snapshot()
    assert snap["SigBatcher.DevicePadded.8192"]["count"] == 1
    assert snap["SigBatcher.DeviceBatches"]["count"] == 1
    assert snap["SigBatcher.DeviceChecked.ed25519"]["count"] == 600


def test_per_scheme_ladder_overrides_default():
    b = SignatureBatcher(metrics=MetricRegistry(), use_device=False,
                         bucket_ladder={"ed25519": (512, 1024)})
    try:
        assert b._ladder_for("ed25519") == (512, 1024)
        assert b._ladder_for("secp256k1") == b._default_ladder
    finally:
        b.close()


def test_ladder_from_occupancy_tunes_floor_per_scheme():
    prof = KernelProfiler()
    for _ in range(4):
        prof.record_occupancy("ed25519", 16384, 16384)   # megabatch-fed
        prof.record_occupancy("secp256r1", 300, 512)     # trickle-fed
    ladders = SignatureBatcher.ladder_from_occupancy(
        profiler=prof, max_batch=32768)
    # floor doubles toward the observed mean with one rung of headroom
    assert ladders["ed25519"] == SignatureBatcher._pow2_ladder(8192, 32768)
    assert ladders["secp256r1"][0] == SignatureBatcher.LADDER_FLOOR


# -- shape-bucket compile stability (satellite: zero post-warmup compiles) --

def test_steady_state_varying_batches_zero_new_compiles_after_warmup():
    """Mixed arrival sizes after warmup must land entirely inside the
    warmed shape set: ladder cuts recur on the rungs and sub-floor tails
    pad to power-of-two buckets, so the (stub) jit cache never grows."""
    prof = KernelProfiler()
    old = get_profiler()
    set_profiler(prof)
    b = SignatureBatcher(metrics=MetricRegistry(), host_crossover=0,
                         max_latency_s=0.01, interactive_latency_s=0.01,
                         bucket_ladder=(8, 16, 32, 64), max_batch=64)

    def stub_start(items, dspan=None):
        n = len(items)
        cap = F.bucket_size(n, floor=8)      # pad exactly like the kernels
        rows = np.zeros((cap,), dtype=np.uint8)
        out = prof.call("stub.ed25519", lambda a: a, rows,
                        live=n, capacity=cap, scheme="ed25519")
        return (out, n), (lambda pending: [True] * pending[1])

    b._start_ed25519 = stub_start
    try:
        # warm phase: one batch per ladder rung
        for rung in (8, 16, 32, 64):
            assert all(b.submit_group([TRIPLE] * rung,
                                      latency_class=BULK).result(timeout=60))
        prof.mark_warm()
        hits0 = prof.compile_totals()["compile_cache_hits"]
        # steady state: arrival sizes that hit no rung exactly — every cut
        # and every padded tail must re-see a warmed shape
        for n in (70, 23, 64, 41, 9, 128, 57):
            assert all(b.submit_group([TRIPLE] * n,
                                      latency_class=BULK).result(timeout=60))
        assert prof.compiles_since_warm() == 0
        assert prof.compile_totals()["compile_cache_hits"] > hits0
        # every dispatched batch fed the occupancy surface
        assert prof.snapshot()["occupancy"]["ed25519"]["batches"] >= 11
    finally:
        b.close()
        set_profiler(old)


# -- latency classes --------------------------------------------------------

def test_interactive_submit_meets_deadline_under_bulk_pressure():
    """An interactive submit behind a wall of queued bulk megabatches must
    resolve via its priority in-flight slot long before the bulk backlog
    drains — the whole point of the latency class split."""
    b = SignatureBatcher(metrics=MetricRegistry(), host_crossover=0,
                         max_latency_s=0.05, interactive_latency_s=0.001,
                         bucket_ladder=(8,), max_batch=8)

    def slow_start(items, dspan=None):
        n = len(items)

        def finish(pending):
            time.sleep(0.25)                 # a busy "device"
            return [True] * n
        return n, finish

    b._start_ed25519 = slow_start
    try:
        bulk_futs = [b.submit_group([TRIPLE] * 8, latency_class=BULK)
                     for _ in range(12)]     # ~1s of stubbed device work
        t0 = time.perf_counter()
        f = b.submit(KP.public, SIG, CONTENT)   # INTERACTIVE by default
        assert f.result(timeout=60) is True
        interactive_s = time.perf_counter() - t0
        # the backlog was still draining when the interactive check landed
        assert sum(1 for g in bulk_futs if g.done()) < len(bulk_futs)
        for g in bulk_futs:
            assert all(g.result(timeout=60))
        bulk_s = time.perf_counter() - t0
        assert interactive_s < bulk_s
        assert interactive_s < 1.5
    finally:
        b.close()


def test_bulk_admission_blocks_at_cap_interactive_always_admitted():
    """max_pending backpressure lands on bulk producers (their enqueue
    blocks at the cap) while interactive submissions are admitted
    instantly — bounded latency under bulk pressure by construction."""
    started = threading.Semaphore(0)
    release = threading.Event()
    b = SignatureBatcher(metrics=MetricRegistry(), use_device=False,
                         max_latency_s=0.001, max_pending=8)
    orig_host = SignatureBatcher._run_host

    def gated_host(items):
        started.release()
        release.wait(timeout=30)
        return orig_host(items)

    b._run_host = gated_host
    try:
        wedged = []
        # wedge the three prep workers one flush at a time (waiting for
        # each to START so consecutive submits cannot coalesce)
        for _ in range(3):
            wedged.append(b.submit_group([TRIPLE], latency_class=BULK))
            assert started.acquire(timeout=10)
        # a fourth plan claims the last host in-flight slot and queues
        # behind the wedged pool workers
        wedged.append(b.submit_group([TRIPLE], latency_class=BULK))
        deadline = time.time() + 10
        while time.time() < deadline and b._inflight_n["host"] < 4:
            time.sleep(0.01)
        assert b._inflight_n["host"] == 4
        # no slots left: this group stays queued, filling the bulk cap
        wedged.append(b.submit_group([TRIPLE] * 8, latency_class=BULK))

        blocked_done = threading.Event()
        extra = []

        def producer():
            extra.append(b.submit_group([TRIPLE], latency_class=BULK))
            blocked_done.set()

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        assert not blocked_done.wait(timeout=0.5)   # admission blocked
        # interactive bypasses admission control entirely
        t0 = time.perf_counter()
        f_int = b.submit_many([TRIPLE], latency_class=INTERACTIVE)[0]
        assert time.perf_counter() - t0 < 1.0
        assert not blocked_done.is_set()

        release.set()
        assert blocked_done.wait(timeout=30)        # producer re-admitted
        t.join(timeout=30)
        assert f_int.result(timeout=30) is True
        for g in wedged + extra:
            assert all(g.result(timeout=30))
    finally:
        release.set()
        b.close()


# -- breaker trip mid-pipeline (chaos-seeded) -------------------------------

@pytest.mark.chaos
@pytest.mark.parametrize("seed", [7, 9001])
def test_breaker_trip_mid_pipeline_zero_lost_futures(seed):
    """A 100%-failing device dispatch under CONCURRENT in-flight batches
    (the double-buffered pipeline, not the sequential chaos test): every
    future still resolves, the breaker trips exactly once, and post-trip
    batches route to host."""
    b = SignatureBatcher(metrics=MetricRegistry(), host_crossover=1,
                         max_latency_s=0.001, breaker_threshold=3,
                         bucket_ladder=(4,), max_batch=4)
    try:
        with inject(FaultRule("batcher.device_dispatch", "raise",
                              detail="ed25519"), seed=seed):
            futs = [b.submit_group([TRIPLE] * 4, latency_class=BULK)
                    for _ in range(10)]
            results = [g.result(timeout=60) for g in futs]
        assert all(len(r) == 4 and all(r) for r in results)   # zero lost
        st = b.breaker_status()["ed25519"]
        assert st["state"] == "open"
        assert st["trips"] == 1
        snap = b.metrics.snapshot()
        assert snap["SigBatcher.InFlight"]["value"] == 0
        assert snap["SigBatcher.BatchFailure"]["count"] >= 3
        assert snap["SigBatcher.BreakerRouted"]["count"] > 0
    finally:
        b.close()


def test_breaker_open_host_route_keeps_occupancy_and_gauges_fresh():
    """Degraded mode must not freeze the observability surface: a
    breaker-routed batch still records occupancy (100% live — no padding)
    and the per-scheme gauges read current state."""
    prof = KernelProfiler()
    old = get_profiler()
    set_profiler(prof)
    reg = MetricRegistry()
    b = SignatureBatcher(metrics=reg, host_crossover=1, max_latency_s=0.001)
    try:
        for _ in range(3):
            b._breakers["ed25519"].record_failure()
        assert b.breaker_status()["ed25519"]["state"] == "open"
        assert all(b.submit_group([TRIPLE] * 4,
                                  latency_class=BULK).result(timeout=60))
        occ = prof.snapshot()["occupancy"]["ed25519"]
        assert occ["batches"] == 1
        assert occ["live_total"] == occ["capacity_total"] == 4
        assert occ["occupancy_pct"] == 100.0
        snap = reg.snapshot()
        assert snap["SigBatcher.BreakerRouted"]["count"] == 4
        assert snap["SigBatcher.ed25519.QueueDepth"]["value"] == 0
        assert snap["SigBatcher.ed25519.InFlight"]["value"] == 0
    finally:
        b.close()
        set_profiler(old)


# -- staging pool -----------------------------------------------------------

def test_staging_pool_reuses_released_buffers():
    pool = StagingPool()
    lease = pool.lease()
    a = lease.take("t.rows", (16, 4), np.uint16)
    assert a.shape == (16, 4) and a.dtype == np.uint16
    lease.release()
    lease.release()                       # idempotent
    lease2 = pool.lease()
    assert lease2.take("t.rows", (16, 4), np.uint16) is a   # recycled
    # a second concurrent take of the same key gets fresh memory
    assert lease2.take("t.rows", (16, 4), np.uint16) is not a
    # different shape/dtype never shares
    assert lease2.take("t.rows", (8, 4), np.uint16) is not a
    stats = pool.stats()
    assert stats["hits"] == 1 and stats["misses"] == 3


def test_staging_pool_release_via_pending_handle():
    pool = StagingPool()
    lease = pool.lease()
    arr = lease.take("t.x", (8,), np.uint8)
    handle = object()
    pool.attach(handle, lease)
    assert pool.stats()["attached"] == 1
    pool.release_for(handle)              # the finish_batch force point
    assert pool.stats()["attached"] == 0
    assert pool.lease().take("t.x", (8,), np.uint8) is arr
    pool.release_for(handle)              # unknown handle: no-op


def test_staging_pool_dropped_lease_is_never_recycled():
    """A lease abandoned mid-dispatch (failure path) must not return its
    possibly-device-aliased buffers to the free lists."""
    pool = StagingPool(max_attached=2)
    leases = [pool.lease() for _ in range(3)]
    arrays = [ls.take("t.y", (4,), np.uint8) for ls in leases]
    handles = [object() for _ in range(3)]   # kept alive: attach keys by id
    for handle, ls in zip(handles, leases):
        pool.attach(handle, ls)
    # the oldest lease was evicted (bounded table) — dropped, not reclaimed
    assert pool.stats()["attached"] == 2
    fresh = pool.lease().take("t.y", (4,), np.uint8)
    assert all(fresh is not a for a in arrays)
