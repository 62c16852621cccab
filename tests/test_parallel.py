"""Multi-chip sharding tests on the virtual 8-device CPU mesh (conftest).

Mirrors the reference's verifier fan-out tests (VerifierTests.kt:53-71:
"verification works with N out-of-process verifiers") — here the fan-out is
SPMD over a Mesh instead of N worker JVMs.
"""
import hashlib

import jax
import numpy as np
import pytest

from corda_tpu.core.crypto import ecmath
from corda_tpu.ops import ed25519 as ed_ops
from corda_tpu.ops import sha256 as sha_ops
from corda_tpu.parallel import (make_mesh, sharded_ecdsa_verify_hybrid,
                                sharded_ed25519_verify_split,
                                sharded_merkle_root,
                                tx_verify_step)

RNG = np.random.default_rng(11)


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= 8, "conftest should provide 8 virtual devices"
    return make_mesh(8)


def _ed_items(n):
    items, want = [], []
    for i in range(n):
        seed = RNG.bytes(32)
        pub = ecmath.ed25519_public_key(seed)
        msg = RNG.bytes(20 + i)
        sig = ecmath.ed25519_sign(seed, msg)
        if i % 3 == 1:
            msg = msg + b"x"  # invalidate
        items.append((pub, sig, msg))
        want.append(ecmath.ed25519_verify(pub, msg, sig))
    return items, want


def test_sharded_ed25519_matches_host(mesh):
    # 64 rows: the 8-rows-a-chip shape the mesh-backed batcher below compiles
    items, want = _ed_items(64)
    *args, precheck = ed_ops.prepare_batch_split(items, device_tables=False)
    w = ed_ops.SPLIT_B_WINDOW
    replicated = jax.NamedSharding(mesh, jax.sharding.PartitionSpec())
    tabs = [jax.device_put(t, replicated)
            for t in (*ed_ops._b_window_table(w, 0),
                      *ed_ops._b_window_table(w, 128))]
    fn = sharded_ed25519_verify_split(mesh)
    ok = np.asarray(fn(*args, *tabs)) & precheck
    assert list(ok) == want
    assert True in ok and False in list(ok)


def test_sharded_hybrid_ecdsa_matches_host(mesh):
    from corda_tpu.ops import weierstrass as wc_ops
    curve = ecmath.SECP256K1
    items, want = [], []
    for i in range(16):
        priv = int.from_bytes(RNG.bytes(32), "little") % (curve.n - 1) + 1
        pub = curve.mul(priv, curve.g)
        msg = RNG.bytes(24 + i)
        r, s = ecmath.ecdsa_sign(curve, priv, msg)
        if i % 3 == 1:
            msg = msg + b"x"
        items.append((pub, msg, r, s))
        want.append(ecmath.ecdsa_verify(curve, pub, msg, r, s))
    *args, precheck = wc_ops.prepare_batch_hybrid_wide(
        items, wc_ops.HYBRID_G_WINDOW)
    fn = sharded_ecdsa_verify_hybrid(mesh)
    ok = np.asarray(fn(*args)) & precheck
    assert list(ok) == want
    assert True in ok and False in list(ok)


def test_sharded_merkle_root_matches_host(mesh):
    leaves_bytes = [hashlib.sha256(bytes([i])).digest() for i in range(32)]
    leaves = sha_ops.digests_from_bytes(leaves_bytes)

    def host_root(hs):
        while len(hs) > 1:
            hs = [hashlib.sha256(hs[i] + hs[i + 1]).digest()
                  for i in range(0, len(hs), 2)]
        return hs[0]

    fn = sharded_merkle_root(mesh)
    got = sha_ops.digests_to_bytes(np.asarray(fn(leaves))[None])[0]
    assert got == host_root(leaves_bytes)


def test_tx_verify_step(mesh):
    items, want = _ed_items(8)
    s_bits, k_bits, neg_a, r_affine, precheck = ed_ops.prepare_batch(items)
    leaves_bytes = [hashlib.sha256(bytes([i, i])).digest() for i in range(16)]
    leaves = sha_ops.digests_from_bytes(leaves_bytes)
    step = tx_verify_step(mesh)
    ok, root = step(s_bits, k_bits, neg_a, r_affine, leaves)
    assert list(np.asarray(ok) & precheck) == want
    def host_root(hs):
        while len(hs) > 1:
            hs = [hashlib.sha256(hs[i] + hs[i + 1]).digest()
                  for i in range(0, len(hs), 2)]
        return hs[0]
    assert sha_ops.digests_to_bytes(np.asarray(root)[None])[0] == host_root(leaves_bytes)


def test_mesh_backed_batcher_matches_host(mesh):
    """VERDICT r2 #7: the SERVICE seam composed with the mesh — a
    SignatureBatcher(mesh=...) shards its device batches over every chip
    and returns the same verdicts as host verification."""
    from corda_tpu.core.crypto import generate_keypair
    from corda_tpu.core.crypto.schemes import (ECDSA_SECP256K1_SHA256,
                                               EDDSA_ED25519_SHA512)
    from corda_tpu.core.crypto.signatures import Crypto
    from corda_tpu.verifier.batcher import SignatureBatcher

    checks, want = [], []
    for i in range(12):
        scheme = (EDDSA_ED25519_SHA512 if i % 2 else ECDSA_SECP256K1_SHA256)
        kp = generate_keypair(scheme, entropy=bytes([0x30 + i]) * 32)
        content = bytes([i]) * 24
        sig = Crypto.sign_with_key(kp, content).bytes
        if i % 4 == 2:
            content = content + b"!"        # invalidate
        checks.append((kp.public, sig, content))
        want.append(Crypto.is_valid(kp.public, sig, content))
    b = SignatureBatcher(mesh=mesh, host_crossover=0, max_latency_s=0.02)
    try:
        futs = b.submit_many(checks)
        got = [f.result(timeout=300) for f in futs]
        assert got == want
        snap = b.metrics.snapshot()
        assert snap["SigBatcher.DeviceChecked"]["count"] >= len(checks)
    finally:
        b.close()
