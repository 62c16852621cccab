"""Device circuit-breaker chaos tests: a 100%-failing device dispatch path
must degrade that scheme to host verification with ZERO dropped or hung
futures, trip the breaker (gauges + trip meter), and recover through a
half-open probe once the device behaves again.

The storm is injected at the ``batcher.device_dispatch`` fault point with
``detail=<scheme>``, so only the targeted scheme degrades. The breaker
clock is injected so cooldown expiry is stepped, not slept.
"""
import pytest

from corda_tpu.core.crypto import generate_keypair
from corda_tpu.core.crypto.schemes import EDDSA_ED25519_SHA512
from corda_tpu.core.crypto.signatures import Crypto
from corda_tpu.testing.faults import FaultRule, inject
from corda_tpu.verifier.batcher import SignatureBatcher

pytestmark = pytest.mark.chaos

SEEDS = [7, 101, 9001]

KP = generate_keypair(EDDSA_ED25519_SHA512, entropy=b"\x71" * 32)
CONTENT = b"breaker chaos content"
SIG = Crypto.sign_with_key(KP, CONTENT).bytes


def make_batcher(clock):
    return SignatureBatcher(host_crossover=1, max_latency_s=0.001,
                            breaker_threshold=3, breaker_cooldown_s=5.0,
                            breaker_clock=lambda: clock[0])


def stub_device(b):
    """Replace the ed25519 device-start seam with an instant all-valid
    kernel: recovery-probe tests must not pay an XLA compile."""
    b._start_ed25519 = lambda items, dspan=None: (
        None, lambda pending: [True] * len(items))


@pytest.mark.parametrize("seed", SEEDS)
def test_storm_trips_breaker_zero_dropped_results(seed):
    """100% device-dispatch failure: every future still resolves (host
    fallback), the breaker opens after exactly `threshold` failures, and
    no further device dispatch is attempted while it is open."""
    clock = [0.0]
    b = make_batcher(clock)
    try:
        with inject(FaultRule("batcher.device_dispatch", "raise",
                              detail="ed25519"), seed=seed) as inj:
            for _ in range(8):
                # sequential: each submit is its own flush → own dispatch
                assert b.submit(KP.public, SIG, CONTENT).result(timeout=60) \
                    is True

            st = b.breaker_status()["ed25519"]
            assert st["state"] == "open"
            assert st["trips"] == 1
            # after the third failure the breaker stopped trying the device
            assert inj.fired("batcher.device_dispatch") == 3

        snap = b.metrics.snapshot()
        assert snap["Breaker.Trips"]["count"] == 1
        assert snap["Breaker.Trips.ed25519"]["count"] == 1
        assert snap["Breaker.State.ed25519"]["value"] == 1        # OPEN
        assert snap["Breaker.State.secp256k1"]["value"] == 0      # untouched
        assert snap["SigBatcher.BatchFailure"]["count"] == 3      # fallbacks
        assert snap["SigBatcher.BreakerRouted"]["count"] == 5     # open-gated
    finally:
        b.close()


@pytest.mark.parametrize("seed", SEEDS)
def test_half_open_probe_reopens_then_restores(seed):
    """Cooldown expiry admits exactly one probe. While the device is still
    broken the probe re-opens the breaker WITHOUT a second trip; once the
    device works the probe closes it and the scheme leaves degradation."""
    clock = [0.0]
    b = make_batcher(clock)
    try:
        with inject(FaultRule("batcher.device_dispatch", "raise",
                              detail="ed25519"), seed=seed) as inj:
            for _ in range(3):
                assert b.submit(KP.public, SIG, CONTENT).result(timeout=60)
            assert b.breaker_status()["ed25519"]["state"] == "open"

            # cooldown elapses but the device is STILL broken: the probe
            # fails and re-opens — no new trip, cooldown restarts
            clock[0] += 6.0
            assert b.submit(KP.public, SIG, CONTENT).result(timeout=60)
            st = b.breaker_status()["ed25519"]
            assert st["state"] == "open"
            assert st["trips"] == 1
            assert inj.fired("batcher.device_dispatch") == 4   # the probe

        # fault gone, device healthy (stubbed: no XLA compile in the fast
        # gate), cooldown elapses again: the next probe closes the breaker
        stub_device(b)
        clock[0] += 6.0
        assert b.submit(KP.public, SIG, CONTENT).result(timeout=60) is True
        st = b.breaker_status()["ed25519"]
        assert st["state"] == "closed"
        assert st["trips"] == 1
        assert b.metrics.snapshot()["Breaker.State.ed25519"]["value"] == 0
    finally:
        b.close()


def test_breaker_trip_surfaces_degraded_in_health():
    """An open breaker rides /readyz as `degraded` (the node serves — host
    path — but ops can see the device is out) and clears on recovery."""
    from corda_tpu.node.rpc import CordaRPCOps
    from corda_tpu.testing import MockNetwork
    from corda_tpu.verifier.service import TpuTransactionVerifierService

    network = MockNetwork()
    network.create_notary_node()
    alice = network.create_node("O=Alice, L=Madrid, C=ES")
    network.start_nodes()
    ops = CordaRPCOps(alice.services, alice.smm)
    svc = TpuTransactionVerifierService(
        workers=1, batcher=SignatureBatcher(use_device=False))
    alice.services.verifier_service = svc
    try:
        health = ops.health()
        assert health["ready"] is True
        assert "degraded" not in health

        breaker = svc.batcher._breakers["ed25519"]
        for _ in range(3):
            breaker.record_failure()
        health = ops.health()
        assert health["ready"] is True        # degraded, NOT unready
        assert health["degraded"]["device_breakers"]["ed25519"]["state"] \
            == "open"

        breaker.clock = lambda: breaker._opened_at + 10.0
        assert breaker.allow()                # half-open probe admitted
        breaker.record_success()
        health = ops.health()
        assert "degraded" not in health
    finally:
        alice.services.verifier_service = None
        svc.shutdown()


@pytest.mark.slow
def test_storm_and_recovery_with_real_kernels():
    """The unstubbed variant: the recovery probe runs the real ed25519
    device kernel (XLA compile and all) — nightly-tier proof that the
    half-open path restores genuine device verification."""
    clock = [0.0]
    b = make_batcher(clock)
    try:
        with inject(FaultRule("batcher.device_dispatch", "raise",
                              detail="ed25519"), seed=7):
            for _ in range(4):
                assert b.submit(KP.public, SIG, CONTENT).result(timeout=60)
            assert b.breaker_status()["ed25519"]["state"] == "open"
        clock[0] += 6.0
        assert b.submit(KP.public, SIG, CONTENT).result(timeout=600) is True
        assert b.breaker_status()["ed25519"]["state"] == "closed"
        assert b.metrics.snapshot()["SigBatcher.DeviceBatches"]["count"] >= 1
    finally:
        b.close()
