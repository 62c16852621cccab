"""Multi-chip parallelism: mesh construction + sharded verification steps.

The reference scales verification by running N stateless verifier JVMs
competing on one work queue (reference Verifier.kt:58-76, VerifierTests.kt:53+).
The TPU-native analog is SPMD: one `jax.sharding.Mesh` over the chips of a
slice, signature batches sharded along the batch axis (the data-parallel
axis), Merkle leaf batches sharded along the leaf axis (the sequence-parallel
axis) with an `all_gather` root combine over ICI.
"""
from .sharded import (  # noqa: F401
    make_mesh,
    make_shard_mesh,
    shard_devices,
    sharded_ed25519_verify_split,
    sharded_ecdsa_verify_hybrid,
    sharded_merkle_root,
    sharded_verify_batch_ed25519,
    sharded_ecdsa_verify_r1_split,
    sharded_verify_batch_secp256k1,
    sharded_verify_batch_secp256k1_words,
    sharded_verify_batch_secp256r1_words,
    tx_verify_step,
)
