"""Host crypto layer tests.

Mirrors the reference's crypto unit tests (CryptoUtilsTest, CompositeKeyTests,
PartialMerkleTreeTest — SURVEY.md §4 tier 1), using the `cryptography` library as an
independent interop oracle for Ed25519/ECDSA.
"""
import hashlib

import pytest

from corda_tpu.core.crypto import (
    SecureHash, b58encode, b58decode, generate_keypair, Crypto,
    EDDSA_ED25519_SHA512, ECDSA_SECP256K1_SHA256, ECDSA_SECP256R1_SHA256,
    CompositeKey, MerkleTree, PartialMerkleTree, MerkleTreeException,
)


def test_secure_hash_basics():
    h = SecureHash.sha256(b"abc")
    assert h.bytes == hashlib.sha256(b"abc").digest()
    assert SecureHash.sha256_twice(b"abc").bytes == hashlib.sha256(
        hashlib.sha256(b"abc").digest()).digest()
    assert SecureHash.parse(h.hex()) == h
    assert SecureHash.zero_hash().bytes == b"\x00" * 32
    with pytest.raises(ValueError):
        SecureHash(b"\x00" * 31)
    # hash_concat is a SINGLE sha256 of the concatenation (SecureHash.kt:36).
    a, b = SecureHash.sha256(b"a"), SecureHash.sha256(b"b")
    assert a.hash_concat(b).bytes == hashlib.sha256(a.bytes + b.bytes).digest()


def test_base58_roundtrip():
    for data in [b"", b"\x00", b"\x00\x00hello", b"corda-tpu", bytes(range(256))]:
        assert b58decode(b58encode(data)) == data
    assert b58encode(b"\x00\x01") == "12"
    with pytest.raises(ValueError):
        b58decode("0OIl")


@pytest.mark.parametrize("scheme", [EDDSA_ED25519_SHA512, ECDSA_SECP256K1_SHA256,
                                    ECDSA_SECP256R1_SHA256])
def test_sign_verify_roundtrip(scheme):
    kp = generate_keypair(scheme, entropy=bytes([7] * 32))
    msg = b"the quick brown fox"
    sig = Crypto.sign_with_key(kp, msg)
    assert sig.is_valid(msg)
    assert sig.verify(msg)
    assert not sig.is_valid(msg + b"!")
    # Tampered signature fails (flip a bit mid-signature).
    bad = bytearray(sig.bytes)
    bad[10] ^= 1
    from corda_tpu.core.crypto.signatures import DigitalSignatureWithKey
    assert not DigitalSignatureWithKey(bytes(bad), kp.public).is_valid(msg)


def test_ed25519_interop_with_cryptography():
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey, Ed25519PublicKey)
    from cryptography.hazmat.primitives import serialization
    seed = bytes(range(32))
    kp = generate_keypair(EDDSA_ED25519_SHA512, entropy=seed)
    oracle = Ed25519PrivateKey.from_private_bytes(seed)
    oracle_pub = oracle.public_key().public_bytes(
        serialization.Encoding.Raw, serialization.PublicFormat.Raw)
    assert kp.public.encoded == oracle_pub
    msg = b"interop message"
    ours = Crypto.sign_with_key(kp, msg)
    # Ed25519 is deterministic: signatures must match byte-for-byte.
    assert ours.bytes == oracle.sign(msg)
    # And their signature verifies under our implementation.
    from corda_tpu.core.crypto.signatures import DigitalSignatureWithKey
    assert DigitalSignatureWithKey(oracle.sign(msg), kp.public).is_valid(msg)


@pytest.mark.parametrize("scheme,curve_name", [(ECDSA_SECP256K1_SHA256, "SECP256K1"),
                                               (ECDSA_SECP256R1_SHA256, "SECP256R1")])
def test_ecdsa_interop_with_cryptography(scheme, curve_name):
    from cryptography.hazmat.primitives.asymmetric import ec
    from cryptography.hazmat.primitives import hashes, serialization
    from corda_tpu.core.crypto.signatures import DigitalSignatureWithKey
    from corda_tpu.core.crypto.keys import sec1_decompress, curve_for_scheme

    msg = b"ecdsa interop"
    # Their key, their signature -> our verify.
    curve = {"SECP256K1": ec.SECP256K1(), "SECP256R1": ec.SECP256R1()}[curve_name]
    oracle = ec.generate_private_key(curve)
    der_sig = oracle.sign(msg, ec.ECDSA(hashes.SHA256()))
    # as the signer emits it, high s or low: Crypto.doVerify takes both
    pub_compressed = oracle.public_key().public_bytes(
        serialization.Encoding.X962, serialization.PublicFormat.CompressedPoint)
    from corda_tpu.core.crypto.keys import PublicKey
    our_view = PublicKey(scheme, pub_compressed)
    assert DigitalSignatureWithKey(der_sig, our_view).is_valid(msg)
    # Our key, our signature -> their verify.
    kp = generate_keypair(scheme, entropy=bytes([3] * 32))
    sig = Crypto.sign_with_key(kp, msg)
    pt = sec1_decompress(curve_for_scheme(scheme), kp.public.encoded)
    nums = ec.EllipticCurvePublicNumbers(pt[0], pt[1], curve)
    nums.public_key().verify(sig.bytes, msg, ec.ECDSA(hashes.SHA256()))  # raises if bad


def test_composite_key_thresholds():
    a = generate_keypair(EDDSA_ED25519_SHA512, entropy=bytes([1] * 32)).public
    b = generate_keypair(EDDSA_ED25519_SHA512, entropy=bytes([2] * 32)).public
    c = generate_keypair(ECDSA_SECP256K1_SHA256, entropy=bytes([3] * 32)).public
    # 2-of-3
    key = CompositeKey.Builder().add_keys(a, b, c).build(threshold=2)
    assert isinstance(key, CompositeKey)
    assert not key.is_fulfilled_by(a)
    assert key.is_fulfilled_by({a, b})
    assert key.is_fulfilled_by({a, c})
    assert key.keys == frozenset({a, b, c})
    # weighted: a has weight 2, alone reaches threshold 2
    wkey = CompositeKey.Builder().add_key(a, 2).add_key(b, 1).build(threshold=2)
    assert wkey.is_fulfilled_by(a)
    assert not wkey.is_fulfilled_by(b)
    # nested
    nested = CompositeKey.Builder().add_key(key, 1).add_key(c, 1).build(threshold=2)
    assert nested.is_fulfilled_by({a, b, c})
    assert not nested.is_fulfilled_by({a, b})  # key fulfilled but c missing
    # builder collapses single child
    assert CompositeKey.Builder().add_key(a).build() == a
    # duplicates rejected
    with pytest.raises(ValueError):
        CompositeKey.Builder().add_keys(a, a).build(threshold=1)
    # encode/decode roundtrip
    assert CompositeKey.decode(nested.encoded) == nested
    # plain-key fulfilment API
    assert a.is_fulfilled_by({a, b})
    assert not a.is_fulfilled_by({b})


def test_merkle_tree_reference_semantics():
    leaves = [SecureHash.sha256(bytes([i])) for i in range(5)]
    tree = MerkleTree.get_merkle_tree(leaves)
    # 5 leaves pad to 8: manual recomputation.
    import hashlib as H
    padded = [h.bytes for h in leaves] + [b"\x00" * 32] * 3

    def combine(xs):
        return [H.sha256(xs[i] + xs[i + 1]).digest() for i in range(0, len(xs), 2)]

    lvl = padded
    while len(lvl) > 1:
        lvl = combine(lvl)
    assert tree.hash.bytes == lvl[0]
    with pytest.raises(MerkleTreeException):
        MerkleTree.get_merkle_tree([])
    # single leaf -> root is the leaf
    single = MerkleTree.get_merkle_tree([leaves[0]])
    assert single.hash == leaves[0]


def test_partial_merkle_tree():
    leaves = [SecureHash.sha256(bytes([i])) for i in range(7)]
    tree = MerkleTree.get_merkle_tree(leaves)
    include = [leaves[1], leaves[4]]
    pmt = PartialMerkleTree.build(tree, include)
    assert pmt.verify(tree.hash, include)
    # wrong root fails
    assert not pmt.verify(SecureHash.sha256(b"x"), include)
    # claiming a non-included hash fails
    assert not pmt.verify(tree.hash, [leaves[0]])
    # subset claim fails (must match exactly)
    assert not pmt.verify(tree.hash, [leaves[1]])
    # building with a hash not in the tree fails
    with pytest.raises(MerkleTreeException):
        PartialMerkleTree.build(tree, [SecureHash.sha256(b"nope")])
