"""Device (TPU) kernels: batched SHA-256, Merkle trees, Ed25519 and secp256k1
signature verification.

These are the hot inner loops of transaction verification (reference call stack
SURVEY.md §3.3: Crypto.doVerify per signature, serializedHash + MerkleTree per
component), re-designed as batched, fixed-shape JAX programs:

- Everything is traced once per (batch-shape) and compiled by XLA; no Python in
  the loop.
- 256-bit field elements are 16×16-bit limbs, uint64 at the seams between
  operations and int32 inside them: a limb product is one native 32-bit
  multiply of balanced digits and a column of them stays near 2^21
  (ops/field.py), so the VPU does the bigint work at its own width.
- Multi-chip fan-out shards the batch dimension over the mesh (corda_tpu.parallel).

x64 note: importing this package enables jax_enable_x64 (field elements cross
the seams as uint64, and the canonical tails compute in 64-bit lanes).
"""
import jax

jax.config.update("jax_enable_x64", True)

from . import sha256  # noqa: E402,F401

__all__ = ["sha256"]
