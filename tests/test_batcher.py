"""SignatureBatcher policy tests: host-crossover routing, per-item fault
isolation, bulk submission (VERDICT r2 #1b/c, weak #9).

Reference analog: the verifier thread-pool seam
(InMemoryTransactionVerifierService.kt:10-18) — here the policy layer in
front of the device kernels.
"""
import threading

import pytest

from corda_tpu.core.crypto import generate_keypair
from corda_tpu.core.crypto.keys import PublicKey
from corda_tpu.core.crypto.schemes import (ECDSA_SECP256K1_SHA256,
                                           ECDSA_SECP256R1_SHA256,
                                           EDDSA_ED25519_SHA512)
from corda_tpu.core.crypto.signatures import Crypto
from corda_tpu.verifier.batcher import SignatureBatcher, _Group, _Pending

KP = generate_keypair(ECDSA_SECP256K1_SHA256, entropy=b"\x61" * 32)
CONTENT = b"batcher policy test content"
SIG = Crypto.sign_with_key(KP, CONTENT).bytes


def test_small_batches_route_to_host():
    """Below the crossover the device dispatch floor (~140 ms) dwarfs host
    verification — small batches must run on host, and without the linger
    wait (the p50@batch=1 path)."""
    b = SignatureBatcher(host_crossover=64)
    try:
        futs = [b.submit(KP.public, SIG, CONTENT) for _ in range(3)]
        assert all(f.result(timeout=30) for f in futs)
        snap = b.metrics.snapshot()
        assert snap["SigBatcher.HostRouted"]["count"] == 3
        assert "SigBatcher.DeviceBatches" not in snap
    finally:
        b.close()


def test_crossover_zero_forces_device():
    b = SignatureBatcher(host_crossover=0, max_latency_s=0.01)
    try:
        futs = b.submit_many([(KP.public, SIG, CONTENT)] * 4)
        assert all(f.result(timeout=120) for f in futs)
        snap = b.metrics.snapshot()
        assert snap["SigBatcher.DeviceBatches"]["count"] >= 1
        assert snap["SigBatcher.DeviceChecked"]["count"] >= 4
    finally:
        b.close()


def test_malformed_member_does_not_poison_batch():
    """Weak #9: one malformed item (garbage key encoding / truncated DER)
    becomes a False verdict for that item alone — siblings still verify."""
    garbage_key = PublicKey(ECDSA_SECP256K1_SHA256, b"\xff" * 33)
    b = SignatureBatcher(host_crossover=0, max_latency_s=0.01)
    try:
        futs = b.submit_many([
            (KP.public, SIG, CONTENT),
            (garbage_key, SIG, CONTENT),          # undecodable point
            (KP.public, b"\x00\x01", CONTENT),     # truncated DER
            (KP.public, SIG, CONTENT),
        ])
        results = [f.result(timeout=120) for f in futs]
        assert results == [True, False, False, True]
    finally:
        b.close()


def test_p50_batch1_latency_skips_linger():
    """A lone submit must not pay max_latency_s linger: with the crossover
    active it dispatches immediately to host. Generous bound (CI boxes)."""
    import time
    b = SignatureBatcher(host_crossover=64, max_latency_s=0.5)
    try:
        b.submit(KP.public, SIG, CONTENT).result(timeout=30)  # warm path
        t0 = time.perf_counter()
        assert b.submit(KP.public, SIG, CONTENT).result(timeout=30)
        elapsed = time.perf_counter() - t0
        assert elapsed < 0.4, f"lone submit lingered: {elapsed:.3f}s"
    finally:
        b.close()


def test_bulk_submit_verdicts_match_individual():
    wrong = Crypto.sign_with_key(KP, b"other").bytes
    b = SignatureBatcher(host_crossover=64)
    try:
        futs = b.submit_many([(KP.public, SIG, CONTENT),
                              (KP.public, wrong, CONTENT)])
        assert [f.result(timeout=30) for f in futs] == [True, False]
    finally:
        b.close()


def test_mixed_drain_preps_schemes_concurrently():
    """Tentpole pin: ONE drain holding ed25519 + k1 + r1 buckets routes each
    bucket to its own prep-pool worker — no serial per-bucket _flush loop on
    the dispatcher thread. With the ed25519 flush wedged on an event, the
    ECDSA buckets of the SAME drain still prep and resolve."""
    ed_kp = generate_keypair(EDDSA_ED25519_SHA512, entropy=b"\x71" * 32)
    r1_kp = generate_keypair(ECDSA_SECP256R1_SHA256, entropy=b"\x72" * 32)
    content = b"mixed drain"
    ed_sig = Crypto.sign_with_key(ed_kp, content).bytes
    k1_sig = Crypto.sign_with_key(KP, content).bytes
    r1_sig = Crypto.sign_with_key(r1_kp, content).bytes

    release, entered = threading.Event(), threading.Event()
    # huge crossover: every bucket takes the host route inside _flush — the
    # pipeline shape under test is identical, with no kernel compiles
    b = SignatureBatcher(host_crossover=10_000, max_latency_s=0.05)
    inner = b._run_host
    ed_id = EDDSA_ED25519_SHA512.scheme_number_id

    def gated_run_host(items):
        if items[0].key.scheme.scheme_number_id == ed_id:
            entered.set()
            assert release.wait(timeout=30)
        return inner(items)

    b._run_host = gated_run_host   # instance shadow of the staticmethod
    try:
        # one submit_many -> one notify -> the dispatcher drains all three
        # scheme buckets in a single pass
        ed_fut, k1_fut, r1_fut = b.submit_many([
            (ed_kp.public, ed_sig, content),
            (KP.public, k1_sig, content),
            (r1_kp.public, r1_sig, content),
        ])
        assert entered.wait(timeout=30)    # ed25519 prep is live and wedged
        assert k1_fut.result(timeout=30) is True
        assert r1_fut.result(timeout=30) is True
        assert not ed_fut.done()
        release.set()
        assert ed_fut.result(timeout=30) is True
        # the overlap gauge saw >= 2 preps in flight at once
        assert b.metrics.snapshot()["SigBatcher.PrepActive"]["max"] >= 2
    finally:
        release.set()
        b.close()


class _CountingLock:
    def __init__(self):
        self._lock = threading.Lock()
        self.acquisitions = 0

    def __enter__(self):
        self.acquisitions += 1
        return self._lock.__enter__()

    def __exit__(self, *exc):
        return self._lock.__exit__(*exc)


def test_group_resolve_single_lock_acquire_per_flush():
    """_resolve batches group fan-in: each group's lock is taken at most
    ONCE per flush, regardless of how many members the flush carries (it
    was once per item — 32k acquires for a 32k single-group flush)."""
    b = SignatureBatcher(use_device=False)
    try:
        g = _Group(6)
        g.lock = _CountingLock()
        items = [_Pending(KP.public, SIG, CONTENT, group=g, index=i)
                 for i in range(6)]
        b._resolve("host", items[:4], [True, False, True, True])
        assert g.lock.acquisitions == 1
        assert not g.future.done()
        b._resolve("host", items[4:], [True, True])
        assert g.lock.acquisitions == 2
        assert g.future.result(timeout=5) == [True, False, True, True,
                                              True, True]
    finally:
        b.close()


def test_group_mixed_schemes_order_and_isolation():
    """submit_group across all three schemes: verdicts return in submission
    order, and a malformed member fails ALONE — its group siblings (in
    other scheme buckets, resolved by other flushes) still verify."""
    ed_kp = generate_keypair(EDDSA_ED25519_SHA512, entropy=b"\x73" * 32)
    r1_kp = generate_keypair(ECDSA_SECP256R1_SHA256, entropy=b"\x74" * 32)
    content = b"group order"
    checks = [
        (ed_kp.public, Crypto.sign_with_key(ed_kp, content).bytes, content),
        (KP.public, b"\x30\x02\x02\x00", content),        # malformed DER
        (r1_kp.public, Crypto.sign_with_key(r1_kp, content).bytes, content),
        (KP.public, Crypto.sign_with_key(KP, content).bytes, content),
    ]
    b = SignatureBatcher(max_latency_s=0.01)
    try:
        assert b.submit_group(checks).result(timeout=120) == [
            True, False, True, True]
        assert b.submit_group([]).result(timeout=5) == []
    finally:
        b.close()


def test_cancelled_future_does_not_wedge_the_dispatcher():
    """Review r3: a caller cancelling its future must not crash the
    dispatcher/finisher — later submissions still resolve."""
    b = SignatureBatcher(host_crossover=0, max_latency_s=0.01)
    try:
        doomed = b.submit(KP.public, SIG, CONTENT)
        doomed.cancel()   # may or may not win the race; either is fine
        after = b.submit_many([(KP.public, SIG, CONTENT)] * 3)
        assert all(f.result(timeout=120) for f in after)
        assert all(b.submit_group([(KP.public, SIG, CONTENT)] * 2)
                   .result(timeout=120))
    finally:
        b.close()


# ---------------------------------------------------------------------------
# The inline route (hold_group / collect_group): a group the planner would
# host-route at once is verified on the thread that collects it
# ---------------------------------------------------------------------------

ED_KP = generate_keypair(EDDSA_ED25519_SHA512, entropy=b"\x75" * 32)
ED_OTHER_KP = generate_keypair(EDDSA_ED25519_SHA512, entropy=b"\x76" * 32)
ED_SIG = Crypto.sign_with_key(ED_KP, CONTENT).bytes

_INLINE_ROWS = {
    "valid": (ED_KP.public, ED_SIG, CONTENT),
    "corrupted_signature": (ED_KP.public,
                            ED_SIG[:-1] + bytes([ED_SIG[-1] ^ 1]), CONTENT),
    "wrong_key": (ED_OTHER_KP.public, ED_SIG, CONTENT),
    "wrong_message": (ED_KP.public, ED_SIG, CONTENT + b"!"),
}


def _spy_threads(obj, name):
    """Wrap ``obj.<name>``; returns the list of thread names it ran on."""
    seen, orig = [], getattr(obj, name)

    def spy(*a, **k):
        seen.append(threading.current_thread().name)
        return orig(*a, **k)

    setattr(obj, name, spy)
    return seen


def _count(batcher, name):
    return batcher.metrics.snapshot().get(name, {}).get("count", 0)


def _stub_device(batcher):
    """Host verdicts in place of the kernels; returns the batch sizes."""
    batches = []

    def device(bucket, items, reason="full", bctx=None):
        batches.append(len(items))
        batcher._mark_device(items)
        batcher._resolve(bucket, items, batcher._run_host(items), bctx)

    batcher._dispatch_device = device
    return batches


@pytest.mark.parametrize("kind", sorted(_INLINE_ROWS))
def test_inline_verdicts_equal_the_queued_path(kind):
    """Same rows, both routes, same verdicts: the inline route runs the
    queue's own host loop (Crypto.is_valid), only on another thread."""
    checks = [_INLINE_ROWS["valid"], _INLINE_ROWS[kind],
              (KP.public, SIG, CONTENT)]
    b = SignatureBatcher()
    try:
        inline = b.collect_group(b.hold_group(checks))
        assert _count(b, "SigBatcher.HostInline") == 3
        queued = b.submit_group(checks, latency_class="interactive") \
            .result(timeout=30)
        assert inline == queued == [True, kind == "valid", True]
    finally:
        b.close()


def test_inline_group_runs_on_the_calling_thread_and_counts_its_rows():
    b = SignatureBatcher()
    seen = _spy_threads(b, "_run_host")
    b._submit_flush = lambda *a, **k: pytest.fail("planner cut a plan")
    checks = [_INLINE_ROWS["valid"], (KP.public, SIG, CONTENT),
              _INLINE_ROWS["wrong_key"]]
    try:
        held = b.hold_group(checks)
        assert b.queue_depths()["ed25519"] == 2
        assert b.collect_group(held) == [True, True, False]
        assert not any(b.queue_depths().values())
        # one host loop a scheme bucket, both on this thread
        assert seen == [threading.current_thread().name] * 2
        assert b._prep_pool is None
        assert _count(b, "SigBatcher.HostRouted") == 3
        assert _count(b, "SigBatcher.Checked") == 3
        assert _count(b, "SigBatcher.HostInline") == 3
        snap = b.metrics.snapshot()
        assert snap["SigBatcher.InFlight"]["value"] == 0
        assert snap["verifier_batch_size"]["count"] == 2
        assert snap["verifier_dispatch_seconds"]["count"] == 2
        assert snap["verifier_finish_seconds"]["count"] == 2
        assert b.collect_group(b.hold_group([])) == []
    finally:
        b.close()


@pytest.mark.parametrize("others,rows,wave_rows,inline", [
    (2, 1, None, True),      # 2 + 1 under the crossover of 4
    (2, 2, None, False),     # 2 + 2 at it: the planner's, all four
    (0, 4, None, False),     # the group alone at it
    (0, 1, 3, True),         # judged by its wave: 3 under
    (0, 1, 4, False),        # ... 4 at it
    (3, 1, 2, False),        # ... and the depth counts beside the wave
])
def test_inline_rule_is_the_planners(others, rows, wave_rows, inline):
    """The queue's depth (this group's rows AND the other flows') or the
    wave's size against host_crossover, read under the batcher's lock. The
    test holds that (re-entrant) lock while it submits, so the depth the
    collector and the planner find is the one it set up, whoever runs
    first. interactive_batch=4 makes a depth of 4 ready at its cap."""
    b = SignatureBatcher(host_crossover=4, interactive_batch=4)
    batches = _stub_device(b)
    row = _INLINE_ROWS["valid"]
    try:
        with b._lock:
            other = [b.hold_group([row]) for _ in range(others)]
            mine = b.hold_group([row] * rows, wave_rows=wave_rows)
            assert b.queue_depths()["ed25519"] == others + rows
        assert b.collect_group(mine) == [True] * rows
        assert _count(b, "SigBatcher.HostInline") == (rows if inline else 0)
        if others + rows >= 4:      # one device batch of every flow's rows
            assert [o.future.result(timeout=30) for o in other] \
                == [[True]] * others
            assert batches == [others + rows]
            assert _count(b, "SigBatcher.DeviceChecked") == others + rows
        assert [b.collect_group(o) for o in other] == [[True]] * others
        assert b.metrics.snapshot()["SigBatcher.InFlight"]["value"] == 0
        assert not any(b.queue_depths().values())
    finally:
        b.close()


def test_concurrent_lone_groups_at_the_crossover_share_one_device_batch():
    """Eight one-signature groups held together against a crossover of 4:
    none is collected inline, the planner cuts ONE device batch of 8, as it
    did when every group went through submit_group."""
    b = SignatureBatcher(host_crossover=4, interactive_batch=8)
    batches = _stub_device(b)
    row = _INLINE_ROWS["valid"]
    try:
        with b._lock:
            held = [b.hold_group([row]) for _ in range(8)]
        assert [b.collect_group(h) for h in held] == [[True]] * 8
        assert batches == [8]
        assert _count(b, "SigBatcher.DeviceChecked") == 8
        assert _count(b, "SigBatcher.HostInline") == 0
        assert _count(b, "SigBatcher.HostRouted") == 0
    finally:
        b.close()


def test_a_group_the_planner_took_first_is_waited_for():
    """Another submission woke the planner, which drained the held rows
    with it: the collector finds nothing to take and reads the future."""
    b = SignatureBatcher()
    row = _INLINE_ROWS["valid"]
    try:
        held = b.hold_group([row, _INLINE_ROWS["wrong_key"]])
        assert b.submit(*row).result(timeout=30) is True
        assert b.collect_group(held) == [True, False]
        assert _count(b, "SigBatcher.HostInline") == 0
        assert _count(b, "SigBatcher.HostRouted") == 3
    finally:
        b.close()


@pytest.mark.parametrize("how", ["use_device_false",
                                 "route_interactive_host"])
def test_host_bucket_takes_the_inline_route_whatever_the_crossover(how):
    b = SignatureBatcher(use_device=how != "use_device_false",
                         host_crossover=0)
    b._submit_flush = lambda *a, **k: pytest.fail("planner cut a plan")
    b.route_interactive_host(how == "route_interactive_host")
    try:
        held = b.hold_group([_INLINE_ROWS["valid"]] * 5)
        assert b.collect_group(held) == [True] * 5
        assert _count(b, "SigBatcher.HostInline") == 5
        # the host bucket's rows were never "routed" away from a device
        assert _count(b, "SigBatcher.HostRouted") == 0
        assert _count(b, "SigBatcher.Checked") == 5
    finally:
        b.close()


def test_hold_on_a_closed_batcher_raises_as_enqueue_does():
    b = SignatureBatcher()
    b.close()
    with pytest.raises(RuntimeError, match="closed"):
        b.hold_group([_INLINE_ROWS["valid"]])
    with pytest.raises(RuntimeError, match="closed"):
        b.submit_group([_INLINE_ROWS["valid"]])
    assert b.metrics.snapshot().get(
        "SigBatcher.InFlight", {"value": 0})["value"] == 0


def test_close_drains_a_group_that_was_held_and_not_yet_collected():
    b = SignatureBatcher()
    held = b.hold_group([_INLINE_ROWS["valid"]])
    b.close()
    assert b.collect_group(held) == [True]
    assert b.metrics.snapshot()["SigBatcher.InFlight"]["value"] == 0
