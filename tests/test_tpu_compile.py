"""Compile the main path's kernels for a DESCRIBED TPU v5e — no chip attached.

The TPU compiler is installed in the sandbox and compiles for a topology
that is described, not attached (on-chip-measurement guide §2): what it
refuses here, the chip refuses too. Nothing runs, so these say nothing
about results or times — chip_smoke.py does that on the chip.

Tier-1 keeps what compiles in seconds (the SHA-256 kernels and the sharded
Merkle root with its all_gather). The EC ladders take minutes each and are
marked slow; their measured seconds are in CHANGES.md (PR 22).

Only one process may load the TPU compiler, and it keeps it until it exits:
the topology is described inside a module-scoped fixture (never at import),
these tests live in this one file, and nothing here starts a child process.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from corda_tpu.core.crypto import ecmath
from corda_tpu.ops import ed25519 as ed_ops
from corda_tpu.ops import field as F
from corda_tpu.ops import sha256 as sha_ops
from corda_tpu.ops import weierstrass as wc_ops
from corda_tpu.parallel.sharded import (AXIS, make_mesh,
                                        sharded_ed25519_verify_split,
                                        sharded_merkle_root)

#: the bucket chip_smoke.py dispatches
ROWS = 8192


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return make_mesh(4, devices=list(topo.devices))


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip (the next run would warn and
    compile again): keep it off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, *args, **kwargs):
    t0 = time.perf_counter()
    compiled = fn.lower(*args, **kwargs).compile()
    mem = compiled.memory_analysis()
    print(f"compiled in {time.perf_counter() - t0:.1f}s: "
          f"code {mem.generated_code_size_in_bytes} B, "
          f"args {mem.argument_size_in_bytes} B, "
          f"temps {mem.temp_size_in_bytes} B, "
          f"aliased {mem.alias_size_in_bytes} B")
    return compiled


# -- tier-1: SHA-256 and the sharded Merkle root --------------------------------

@pytest.mark.parametrize("name,fn,shape", [
    ("hash_pairs", sha_ops.hash_pairs, (4096, 16)),
    ("sha256_blocks", sha_ops._sha256_blocks_impl, (4096, 2, 16)),
    ("merkle_root", sha_ops._merkle_root_impl, (512, 8, 8)),
])
def test_sha256_kernel_compiles_for_v5e(one_chip, name, fn, shape):
    compiled = _compile(
        fn, jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one_chip))
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0


def test_sharded_merkle_root_compiles_for_v5e_mesh(mesh4):
    leaves = jax.ShapeDtypeStruct(
        (4096, 8), jnp.uint32, sharding=NamedSharding(mesh4, P(AXIS, None)))
    compiled = _compile(sharded_merkle_root(mesh4), leaves)
    assert "all-gather" in compiled.as_text()


# -- tier-1: a field operation is a handful of program steps ---------------------

#: Fusions (program steps) of the optimised v5e module, as PR 32 left them.
#: On the chip the steps set a field operation's time, not its multiplies:
#: the same product placed with ``.at[].add`` was 54 fusions and 5x slower
#: (PERF.md, PR 32). Limits leave a third of room over today's counts.
FIELD_STEPS = [
    ("mul.k1", lambda a, b: F.mul(a, b, F.PSECP), 12, 16),
    ("sqr.25519", lambda a, b: F.sqr(a, F.P25519), 25, 33),
    ("sub.k1", lambda a, b: F.sub(a, b, F.PSECP), 3, 5),
    ("mul.r1", lambda a, b: F.mul(a, b, F.PSECR1), 18, 24),
    ("k1.dbl", lambda a, b: wc_ops.dbl((a, b, a), wc_ops.CURVES["secp256k1"]),
     86, 115),
]


@pytest.mark.parametrize("name,fn,today,limit", FIELD_STEPS,
                         ids=[row[0] for row in FIELD_STEPS])
def test_a_field_operation_is_a_handful_of_program_steps(one_chip, name, fn,
                                                         today, limit):
    from corda_tpu.tools.fieldsteps import hlo_counts
    el = jax.ShapeDtypeStruct((ROWS, F.NLIMB), jnp.uint64, sharding=one_chip)
    counts = hlo_counts(_compile(jax.jit(fn), el, el).as_text())
    print(name, counts)
    assert counts["fusions"] <= limit, (name, counts, today)
    # no slice update survives into the program: every shifted add is a pad
    assert "dynamic-update-slice" not in counts and "scatter" not in counts


# -- slow: the EC ladders at the bucket the smoke dispatches --------------------

def _tile(base, n):
    return (base * (n // len(base) + 1))[:n]


def _ed_items(n):
    rng = np.random.default_rng(0)
    base = []
    for _ in range(4):
        sk, msg = rng.bytes(32), rng.bytes(64)
        base.append((ecmath.ed25519_public_key(sk),
                     ecmath.ed25519_sign(sk, msg), msg))
    return _tile(base, n)


def _ecdsa_items(curve, n):
    rng = np.random.default_rng(0)
    base = []
    for _ in range(4):
        priv = int.from_bytes(rng.bytes(32), "little") % (curve.n - 1) + 1
        msg = rng.bytes(64)
        base.append((curve.mul(priv, curve.g), msg,
                     *ecmath.ecdsa_sign(curve, priv, msg)))
    return _tile(base, n)


def _service_kernel(scheme):
    """(the scheme's one jit handle, real prep args, static kwargs): what
    the service path, ``verify_batch`` and the tools all dispatch."""
    if scheme == "ed25519":
        *args, _ = ed_ops.prepare_batch_split(_ed_items(ROWS),
                                              ed_ops.SPLIT_B_WINDOW)
        return (ed_ops._verify_kernel_split, args,
                {"w": ed_ops.SPLIT_B_WINDOW})
    if scheme == "secp256k1":
        *args, _ = wc_ops.prepare_batch_hybrid_wide(
            _ecdsa_items(ecmath.SECP256K1, ROWS), wc_ops.HYBRID_G_WINDOW)
        return (wc_ops._verify_kernel_hybrid_wide, args,
                {"g_w": wc_ops.HYBRID_G_WINDOW})
    *args, _, _ = wc_ops.prepare_batch_r1_split(
        ecmath.SECP256R1, _ecdsa_items(ecmath.SECP256R1, ROWS))
    return (wc_ops._verify_kernel_r1_split, args,
            {"curve_name": "secp256r1", "w": wc_ops.R1_G_WINDOW})


@pytest.mark.slow
@pytest.mark.parametrize("scheme", ["ed25519", "secp256k1", "secp256r1"])
def test_service_ec_kernel_compiles_for_v5e(one_chip, scheme):
    fn, args, static = _service_kernel(scheme)
    compiled = _compile(fn, *_shapes(args, one_chip), **static)
    # fits one v5e chip's 16 GB with room for three batches in flight
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 2 << 30


@pytest.mark.slow
def test_sharded_ed25519_split_compiles_for_v5e_mesh(mesh4):
    w = ed_ops.SPLIT_B_WINDOW
    *head, _ = ed_ops.prepare_batch_split(_ed_items(ROWS), w,
                                          device_tables=False)
    tabs = (*ed_ops._b_window_table(w, 0), *ed_ops._b_window_table(w, 128))
    specs = (P(None, AXIS), P(None, None, AXIS), P(AXIS, None, None),
             P(AXIS, None), *((P(None, None),) * 6))
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype,
                                 sharding=NamedSharding(mesh4, s))
            for a, s in zip((*head, *tabs), specs)]
    compiled = _compile(sharded_ed25519_verify_split(mesh4), *args)
    # dp-sharded: no collective belongs in this program
    text = compiled.as_text()
    assert "all-gather" not in text and "all-reduce" not in text
