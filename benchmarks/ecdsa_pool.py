"""The seeded pool of ECDSA waves for the ``ecdsawaves`` driver: party keys,
signatures as the reference's signer emits them, and the corrupted rows.

The signer is the ``cryptography`` package's ECDSA with RFC 6979 nonces
(``deterministic_signing=True``), so a seed gives a byte-identical pool on
any number of cores; it does not normalise ``s``, as BouncyCastle's
``SHA256withECDSA`` (Crypto.doSign) does not, so about half the signatures
carry ``s > n / 2``. Signing costs ~0.35 ms a row on one core: pools of
``PARALLEL_FROM`` rows or more are signed by worker processes, each this
file run as a script (``python ecdsa_pool.py <module>:<function>``, one
pickled job on stdin, the pickled answer on stdout), which touch nothing of
JAX or ``corda_tpu``.
"""
from __future__ import annotations

import functools
import importlib
import os
import pickle
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: group orders (SEC 2); only a curve listed here can be a deployment's scheme
ORDERS = {
    "secp256k1":
        0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141,
    "secp256r1":
        0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551,
}
CORRUPTIONS = ("flipped last signature byte", "another signer's key",
               "altered message", "non-minimal DER (padded leading zero)",
               "s replaced by s + n")
#: the kinds whose signature is no strict DER of two integers of at most 32
#: bytes (``s + n`` needs 33): a word-form prep refuses them unparsed
NO_WORDS = (3, 4)
PARALLEL_FROM = 4096
MAX_WORKERS = 8


class SignerUnavailable(Exception):
    """The installed OpenSSL cannot sign with RFC 6979 nonces."""


def _curve(scheme: str):
    from cryptography.hazmat.primitives.asymmetric import ec
    return {"secp256k1": ec.SECP256K1, "secp256r1": ec.SECP256R1}[scheme]()


@functools.cache
def _algorithm():
    from cryptography.exceptions import UnsupportedAlgorithm
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec
    try:
        alg = ec.ECDSA(hashes.SHA256(), deterministic_signing=True)
        ec.derive_private_key(1, ec.SECP256R1()).sign(b"probe", alg)
    except (TypeError, UnsupportedAlgorithm) as e:
        raise SignerUnavailable(
            f"cryptography/OpenSSL has no deterministic ECDSA: {e!r}")
    return alg


def sign_rows(job) -> list[bytes]:
    """``(scheme, private scalars, signer index per row, messages)`` -> one
    DER signature per row. A top-level function of an importable module, so
    that a spawned worker can be handed it."""
    from cryptography.hazmat.primitives.asymmetric import ec
    scheme, scalars, signer, msgs = job
    alg = _algorithm()
    keys = [ec.derive_private_key(d, _curve(scheme)) for d in scalars]
    return [keys[k].sign(m, alg) for k, m in zip(signer, msgs)]


def parallel_map(target: str, jobs: list, rows: int) -> list:
    """``[fn(job) for job in jobs]`` for ``target`` = ``module:function`` of
    a module under benchmarks/, on several cores when ``rows`` is large.
    Each worker is a fresh interpreter, never a fork: the parent holds the
    chip, and a worker imports only what ``module`` imports."""
    module, name = target.split(":")
    workers = min(MAX_WORKERS, len(jobs), os.cpu_count() or 1)
    if rows < PARALLEL_FROM or workers < 2:
        fn = getattr(importlib.import_module(module), name)
        return [fn(job) for job in jobs]

    def one(job):
        done = subprocess.run([sys.executable, __file__, target],
                              input=pickle.dumps(job), capture_output=True)
        if done.returncode != 0:
            raise RuntimeError(f"worker {target} failed: "
                               f"{done.stderr.decode(errors='replace')[-600:]}")
        return pickle.loads(done.stdout)

    with ThreadPoolExecutor(workers) as ex:
        return list(ex.map(one, jobs))


def der_int(v: int) -> bytes:
    body = v.to_bytes(v.bit_length() // 8 + 1, "big")    # minimal, positive
    return b"\x02" + bytes([len(body)]) + body


def der_sig(r_body: bytes, s_body: bytes) -> bytes:
    body = r_body + s_body
    return b"\x30" + bytes([len(body)]) + body


def corrupt(kind: int, scheme: str, pub, sig, msg, other_pub):
    """One row with corruption ``CORRUPTIONS[kind]`` applied."""
    from cryptography.hazmat.primitives.asymmetric.utils import \
        decode_dss_signature
    if kind == 0:
        return pub, sig[:-1] + bytes([sig[-1] ^ 1]), msg
    if kind == 1:
        return other_pub, sig, msg
    if kind == 2:
        return pub, sig, msg[:-1] + bytes([msg[-1] ^ 1])
    r, s = decode_dss_signature(sig)
    if kind == 3:       # the same (r, s), r with one more leading zero byte
        padded = der_int(r)
        padded = b"\x02" + bytes([padded[1] + 1]) + b"\x00" + padded[2:]
        return pub, der_sig(padded, der_int(s)), msg
    return pub, der_sig(der_int(r), der_int(s + ORDERS[scheme])), msg


def public_keys(scheme: str, scalars) -> list[bytes]:
    """The compressed SEC1 encodings of the keys of ``scalars``."""
    from cryptography.hazmat.primitives import serialization
    from cryptography.hazmat.primitives.asymmetric import ec
    point = (serialization.Encoding.X962,
             serialization.PublicFormat.CompressedPoint)
    return [ec.derive_private_key(d, _curve(scheme)).public_key()
            .public_bytes(*point) for d in scalars]


def build_pool(seed: int, waves: int, wave_size: int, n_keys: int,
               corrupt_every: int, scheme: str = "secp256k1"):
    """``waves`` lists of ``wave_size`` (compressed SEC1 key, DER signature,
    message) rows, and per wave a dict row index -> corruption kind. Keys
    repeat as on a ledger (``n_keys`` parties); every message is a fresh
    32-byte id; 1 row in ``corrupt_every`` is corrupted, the five kinds in
    rotation through the whole pool."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xEC5A])
    order = ORDERS[scheme]
    scalars = [int.from_bytes(rng.bytes(32), "big") % (order - 1) + 1
               for _ in range(n_keys)]
    pubs = public_keys(scheme, scalars)
    _algorithm()                    # refuse here, not in a worker
    plans = []
    for _w in range(waves):
        signer = rng.integers(0, n_keys, size=wave_size)
        blob = rng.bytes(32 * wave_size)
        msgs = [blob[32 * i:32 * i + 32] for i in range(wave_size)]
        bad_rows = rng.choice(wave_size, size=wave_size // corrupt_every,
                              replace=False)
        plans.append((signer, msgs, sorted(int(r) for r in bad_rows)))
    jobs = [(scheme, scalars, [int(k) for k in signer], msgs)
            for signer, msgs, _bad in plans]
    signed = parallel_map("ecdsa_pool:sign_rows", jobs, waves * wave_size)
    pool, corrupted, turn = [], [], 0
    for (signer, msgs, bad_rows), sigs in zip(plans, signed):
        rows = [(pubs[k], sig, msg)
                for k, sig, msg in zip(signer, sigs, msgs)]
        bad = {}
        for i in bad_rows:
            bad[i] = turn % len(CORRUPTIONS)
            turn += 1
            rows[i] = corrupt(bad[i], scheme, *rows[i],
                              pubs[(signer[i] + 1) % n_keys])
        pool.append(rows)
        corrupted.append(bad)
    return pool, corrupted


def high_s_share(pool, scheme: str) -> float:
    """Share of the pool's decodable signatures with ``s > n / 2``."""
    from cryptography.hazmat.primitives.asymmetric.utils import \
        decode_dss_signature
    high = total = 0
    for rows in pool:
        for _pub, sig, _msg in rows:
            try:
                _r, s = decode_dss_signature(sig)
            except ValueError:
                continue
            total += 1
            high += s > ORDERS[scheme] // 2
    return high / max(1, total)


if __name__ == "__main__":
    _module, _name = sys.argv[1].split(":")
    _fn = getattr(importlib.import_module(_module), _name)
    _out = pickle.dumps(_fn(pickle.loads(sys.stdin.buffer.read())))
    sys.stdout.buffer.write(_out)
