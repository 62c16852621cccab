#!/usr/bin/env python3
"""chip_smoke.py — the one-command proof that corda_tpu runs on an attached TPU.

    python3 chip_smoke.py             # one chip: device, native, kernels,
                                      #           service, ledger
    python3 chip_smoke.py --chips 4   # four chips: device, native, mesh only

One process; each phase prints one JSON line when it ends; the first failed
phase ends the run with a non-zero exit. The last stdout line of a passing run
is ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.

There is no option that lets this pass on a CPU: the ``device`` phase fails
unless JAX reports a TPU. After every phase that touches a SignatureBatcher
the run fails if the batcher's host fallback or a circuit breaker fired —
those keep a production node answering, and would otherwise let a kernel the
chip refuses pass with correct verdicts and exit 0.

The phase functions take their sizes as arguments so tests/test_chip_smoke.py
can rehearse them at tiny sizes on the CPU backend.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import pathlib
import statistics
import subprocess
import sys
import threading
import time

ROOT = pathlib.Path(__file__).resolve().parent

#: One bulk-ladder rung above every crossover: exactly one compile per scheme.
ROWS = 8192
UNIQUE = 512
#: Every CORRUPT_EVERY-th row is corrupted (128 of 8192 rows, 1.6%).
CORRUPT_EVERY = 64
#: secp256r1 is "not run": its 8192-bucket compile for a described
#: v5e did not finish in the ten minutes ISSUE 22 allows (see CHANGES.md).
SCHEMES = ("ed25519", "secp256k1")
NOT_RUN = ("secp256r1",)
FUTURE_TIMEOUT_S = 1100
#: The driver allows 1200 s. A device call or a pool shutdown that hangs must
#: still end in a failure line and a non-zero exit, not in the driver's kill.
DEADLINE_S = 1150


#: per-scheme stream of the seeded generator
_SCHEME_IDS = {"ed25519": 0, "secp256k1": 1, "secp256r1": 2}


class PhaseFailed(Exception):
    pass


def _require(cond, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


# -- shared checks -------------------------------------------------------------

def batcher_counters(batcher) -> dict:
    m = batcher.metrics
    return {name: m.meter(f"SigBatcher.{name}").count
            for name in ("DeviceChecked", "HostRouted", "BatchFailure",
                         "BreakerRouted")}


def check_batcher(batcher, rows_sent: int | None = None) -> dict:
    """Fail unless no device failure was swallowed. With ``rows_sent`` the
    phase also claims the device route: every row device-checked, none
    host-routed. Returns the counters for the phase's line."""
    c = batcher_counters(batcher)
    _require(c["BatchFailure"] == 0,
             f"SigBatcher.BatchFailure = {c['BatchFailure']}: a device batch "
             "failed and was verified on the host instead")
    _require(c["BreakerRouted"] == 0,
             f"SigBatcher.BreakerRouted = {c['BreakerRouted']}")
    tripped = {s: st for s, st in batcher.breaker_status().items()
               if st["state"] != "closed" or st["trips"]}
    _require(not tripped, f"device circuit breaker engaged: {tripped}")
    if rows_sent is not None:
        _require(c["DeviceChecked"] >= rows_sent,
                 f"SigBatcher.DeviceChecked = {c['DeviceChecked']} < "
                 f"{rows_sent} rows sent")
        _require(c["HostRouted"] == 0,
                 f"SigBatcher.HostRouted = {c['HostRouted']}")
    return c


#: JAX's own persistent-cache events, counted from the device phase on.
#: "cache_misses" fires when an entry is WRITTEN (a compile of 1 s or more
#: that the cache did not hold). A warm run re-traces every kernel (the
#: flight recorder books that as a compile) but writes no EC kernel.
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": 0,
                 "/jax/compilation_cache/cache_misses": 0}


def _count_cache_event(event: str, **_kw) -> None:
    if event in _CACHE_EVENTS:
        _CACHE_EVENTS[event] += 1


def compile_report() -> dict:
    """Process-wide totals so far: first calls per kernel (trace + lower +
    compile-or-cache-load + first dispatch, as the flight recorder books
    them) and the persistent cache's hits and misses."""
    from corda_tpu.observability import get_profiler
    prof = get_profiler()
    kernels = prof.snapshot()["kernels"]
    totals = prof.compile_totals()
    return {"compiles": totals["compiles"],
            "compile_s": round(totals["compile_s_total"], 1),
            "compile_s_by_kernel": {n: round(k["compile_s"], 1)
                                    for n, k in kernels.items()
                                    if k["compiles"]},
            "persistent_cache_hits":
                _CACHE_EVENTS["/jax/compilation_cache/cache_hits"],
            "persistent_cache_writes":
                _CACHE_EVENTS["/jax/compilation_cache/cache_misses"]}


def devices_of(arr) -> set:
    return {s.device for s in arr.addressable_shards}


def check_placement(what: str, arr, n_chips: int) -> None:
    """Code that has only ever run on virtual devices may put every shard
    on the first chip: require ``n_chips`` distinct devices."""
    devs = devices_of(arr)
    _require(len(devs) == n_chips,
             f"{what}: shards sit on {len(devs)} device(s) "
             f"{sorted(str(d) for d in devs)}, want {n_chips} distinct")


def merkle_root_hashlib(leaves: list[bytes]) -> bytes:
    """Plain reference: zero-pad to a power of two, single-SHA-256 combine."""
    n = 1
    while n < len(leaves):
        n <<= 1
    level = list(leaves) + [bytes(32)] * (n - len(leaves))
    while len(level) > 1:
        level = [hashlib.sha256(level[i] + level[i + 1]).digest()
                 for i in range(0, len(level), 2)]
    return level[0]


# -- seeded inputs -------------------------------------------------------------

def signed_rows(scheme: str, rows: int, unique: int, seed: int,
                corrupt_every: int = CORRUPT_EVERY):
    """``rows`` (PublicKey, signature, message) checks for one scheme:
    ``unique`` distinct honest signatures tiled, with every
    ``corrupt_every``-th row corrupted (flipped signature byte / another
    signer's key / altered message, in rotation). Returns (checks, corrupted
    row indices)."""
    import numpy as np

    from corda_tpu.core.crypto import ecmath
    from corda_tpu.core.crypto.keys import PublicKey, sec1_compress
    from corda_tpu.core.crypto.schemes import (
        ECDSA_SECP256K1_SHA256, ECDSA_SECP256R1_SHA256, EDDSA_ED25519_SHA512)

    rng = np.random.default_rng([seed, _SCHEME_IDS[scheme]])
    base = []
    for _ in range(unique):
        msg = rng.bytes(64)
        if scheme == "ed25519":
            sk = rng.bytes(32)
            key = PublicKey(EDDSA_ED25519_SHA512,
                            ecmath.ed25519_public_key(sk))
            sig = ecmath.ed25519_sign(sk, msg)
        else:
            curve, spec = ((ecmath.SECP256K1, ECDSA_SECP256K1_SHA256)
                           if scheme == "secp256k1"
                           else (ecmath.SECP256R1, ECDSA_SECP256R1_SHA256))
            priv = int.from_bytes(rng.bytes(32), "little") % (curve.n - 1) + 1
            key = PublicKey(spec, sec1_compress(curve,
                                                curve.mul(priv, curve.g)))
            r, s = ecmath.ecdsa_sign(curve, priv, msg)
            if len(base) % 2:     # the n - s twin: Crypto.doVerify takes both
                s = curve.n - s
            sig = ecmath.ecdsa_sig_to_der(r, s)
        base.append((key, sig, msg))
    checks, corrupted = [], []
    for i in range(rows):
        key, sig, msg = base[i % unique]
        if i % corrupt_every == 0:
            corrupted.append(i)
            kind = (i // corrupt_every) % 3
            if kind == 0:
                sig = sig[:-1] + bytes([sig[-1] ^ 1])
            elif kind == 1:
                key = base[(i + 1) % unique][0]
            else:
                msg = msg + b"!"
        checks.append((key, sig, msg))
    return checks, corrupted


def host_reference(checks) -> list[bool]:
    """The plain host verdicts (Crypto.is_valid), one per distinct check."""
    from corda_tpu.core.crypto.signatures import Crypto
    memo: dict = {}
    out = []
    for key, sig, msg in checks:
        k = (key.encoded, sig, msg)
        if k not in memo:
            memo[k] = bool(Crypto.is_valid(key, sig, msg))
        out.append(memo[k])
    return out


def compare_verdicts(scheme: str, got, checks, corrupted) -> None:
    want = host_reference(checks)
    bad = set(corrupted)
    _require(all(want[i] == (i not in bad) for i in range(len(checks))),
             f"{scheme}: the host reference disagrees with the known "
             "corrupted set")
    got = [bool(v) for v in got]
    diff = [i for i, (g, w) in enumerate(zip(got, want)) if g != w]
    _require(len(got) == len(want) and not diff,
             f"{scheme}: {len(diff)} verdicts differ from the host "
             f"reference (first rows {diff[:8]})")


def dummy_transactions(distinct: int, seed: int, bad: tuple):
    """``distinct`` single-signature Ed25519 SignedTransactions over the
    dummy contract; base indices in ``bad`` carry a corrupted signature."""
    from corda_tpu.core.contracts.structures import Command, TransactionState
    from corda_tpu.core.crypto import generate_keypair
    from corda_tpu.core.crypto.signatures import (Crypto,
                                                  DigitalSignatureWithKey)
    from corda_tpu.core.identity import Party
    from corda_tpu.core.transactions.signed import SignedTransaction
    from corda_tpu.core.transactions.wire import WireTransaction
    from corda_tpu.testing.dummy import DummyContract, DummyState

    ent = hashlib.sha256(b"chip_smoke/%d" % seed).digest()
    notary = Party("O=Smoke Notary, L=Zurich, C=CH",
                   generate_keypair(entropy=ent).public)
    stxs = []
    for i in range(distinct):
        kp = generate_keypair(
            entropy=hashlib.sha256(ent + i.to_bytes(4, "big")).digest())
        wtx = WireTransaction(
            outputs=(TransactionState(DummyState(i, (kp.public,)), notary),),
            commands=(Command(DummyContract.Create(), (kp.public,)),),
            notary=notary, must_sign=(kp.public,))
        sig = Crypto.sign_with_key(kp, wtx.id.bytes)
        if i in bad:
            sig = DigitalSignatureWithKey(
                sig.bytes[:-1] + bytes([sig.bytes[-1] ^ 1]), sig.by)
        stxs.append(SignedTransaction.of(wtx, [sig]))
    return stxs


# -- phases ----------------------------------------------------------------------

def phase_device(min_count: int = 1) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "jax": jax.__version__}
    _require(info["platform"] == "tpu",
             f"JAX found no TPU: platform is {info['platform']!r} "
             f"({info['kind']}, {info['count']} device(s))")
    _require(len(devs) >= min_count,
             f"need {min_count} chips, JAX sees {len(devs)}")
    from corda_tpu.utils.compile_cache import enable_compile_cache
    info["cache_dir"] = enable_compile_cache()
    jax.monitoring.register_event_listener(_count_cache_event)
    # empty-dispatch round trip: a trivial jitted op, each reading ended by
    # a host copy
    bump = jax.jit(lambda x: x + 1)
    x = jnp.zeros((8,), jnp.int32)
    np.asarray(bump(x))
    readings = []
    for _ in range(20):
        t0 = time.perf_counter()
        np.asarray(bump(x))
        readings.append((time.perf_counter() - t0) * 1e3)
    info["dispatch_roundtrip_ms_median"] = statistics.median(readings)
    info["dispatch_roundtrip_ms_max"] = max(readings)
    return info


def phase_native() -> dict:
    """Build native/ from the committed sources and require every loader to
    find its library — a fresh copy otherwise takes the pure-Python preps
    without a word. Never trusts a .so it did not just build."""
    loaders = ("corda_tpu.ops.scalarprep", "corda_tpu.storage.kvstore",
               "corda_tpu.consensus.raftcore")
    _require(not any(m in sys.modules for m in loaders),
             "a native loader was imported before the build")
    t0 = time.perf_counter()
    try:
        mk = subprocess.run(["make", "-B", "-C", str(ROOT / "native")],
                            capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        raise PhaseFailed(f"make -C native did not run: {e!r}")
    _require(mk.returncode == 0,
             f"make -C native failed (rc {mk.returncode}): "
             f"{mk.stderr.strip()[-800:]}")
    build_s = time.perf_counter() - t0
    from corda_tpu.consensus import raftcore
    from corda_tpu.ops import scalarprep
    from corda_tpu.storage import kvstore
    found = {"scalarmath": scalarprep.available(),
             "kvlog": kvstore.NATIVE_AVAILABLE,
             "raftcore": raftcore.NATIVE_RAFT_AVAILABLE}
    _require(all(found.values()), f"native libraries not loaded: {found}")
    return {"build_s": round(build_s, 1), **found}


def phase_kernels(seed: int, rows: int = ROWS, unique: int = UNIQUE,
                  schemes=SCHEMES, corrupt_every: int = CORRUPT_EVERY,
                  merkle_txs: int = 512, service=None) -> dict:
    """One bulk group per scheme through the verifier service's batcher,
    the schemes submitted together so the prep pool compiles them side by
    side; then the device Merkle path once."""
    from corda_tpu.observability import get_profiler
    from corda_tpu.utils.metrics import MetricRegistry
    from corda_tpu.verifier.service import TpuTransactionVerifierService

    if service is None:
        service = TpuTransactionVerifierService(metrics=MetricRegistry())
    batcher = service.batcher
    try:
        work = {s: signed_rows(s, rows, unique, seed, corrupt_every)
                for s in schemes}
        futures = {s: batcher.submit_group(checks)
                   for s, (checks, _) in work.items()}
        for s, fut in futures.items():
            compare_verdicts(s, fut.result(timeout=FUTURE_TIMEOUT_S),
                             *work[s])
        counters = check_batcher(batcher, rows_sent=rows * len(schemes))
    finally:
        service.shutdown()
    merkle = _merkle_device_path(seed, merkle_txs)
    get_profiler().mark_warm()
    return {"rows_per_scheme": rows, "schemes": list(schemes),
            **{s: "not run" for s in NOT_RUN if s not in schemes},
            "corrupted_rows_per_scheme": len(next(iter(work.values()))[1]),
            **counters, **merkle, **compile_report()}


def _merkle_device_path(seed: int, n_txs: int) -> dict:
    """batch_roots and verify_filtered_batch with an explicit small device
    crossover: roots equal hashlib's, every proof verifies, a tampered
    one does not."""
    from corda_tpu.core.contracts.structures import Command
    from corda_tpu.core.crypto.secure_hash import SecureHash
    from corda_tpu.core.transactions.batch_merkle import (
        batch_roots, verify_filtered_batch)
    from corda_tpu.core.transactions.filtered import FilteredTransaction

    stxs = dummy_transactions(min(n_txs, 64), seed, bad=())
    wtxs = [stxs[i % len(stxs)].tx for i in range(n_txs)]
    leaf_lists = [w.available_component_hashes for w in wtxs]
    n_leaves = sum(len(hs) for hs in leaf_lists)
    roots = batch_roots(leaf_lists, device_crossover=2)
    for w, hs, root in zip(wtxs, leaf_lists, roots):
        want = merkle_root_hashlib([h.bytes for h in hs])
        _require(root.bytes == want and root == w.id,
                 "device Merkle root differs from hashlib's")
    ftxs = [w.build_filtered_transaction(lambda c: isinstance(c, Command))
            for w in wtxs]
    ftxs.append(FilteredTransaction(SecureHash.sha256(b"tampered"),
                                    ftxs[0].filtered_leaves,
                                    ftxs[0].partial_merkle_tree))
    got = verify_filtered_batch(ftxs, device_crossover=2)
    _require(got == [True] * n_txs + [False],
             "device tear-off verification disagrees with the known set")
    return {"merkle_txs": n_txs, "merkle_leaves": n_leaves}


def phase_service(seed: int, n_tx: int = ROWS, distinct: int = 256,
                  bad: tuple = (3, 77, 200)) -> dict:
    """The call the flows make: verify_signed, one interactive submit per
    transaction. Existing batcher arguments make the only flush the count
    reaching ``n_tx``, so it lands on the bucket ``kernels`` compiled."""
    from corda_tpu.core.crypto.signatures import SignatureException
    from corda_tpu.observability import get_profiler
    from corda_tpu.testing.services import MockServices
    from corda_tpu.utils.metrics import MetricRegistry
    from corda_tpu.verifier.batcher import SignatureBatcher
    from corda_tpu.verifier.service import TpuTransactionVerifierService

    stxs = dummy_transactions(distinct, seed, bad)
    registry = MetricRegistry()
    service = TpuTransactionVerifierService(
        metrics=registry,
        batcher=SignatureBatcher(metrics=registry, host_crossover=0,
                                 interactive_batch=n_tx,
                                 interactive_latency_s=60.0))
    services = MockServices()
    try:
        futures = [service.verify_signed(stxs[i % distinct], services)
                   for i in range(n_tx)]
        wrong = []
        for i, fut in enumerate(futures):
            try:
                fut.result(timeout=FUTURE_TIMEOUT_S)
                verified = True
            except SignatureException:
                verified = False
            if verified == ((i % distinct) in bad):
                wrong.append(i)
        _require(not wrong, f"{len(wrong)} transactions got the wrong "
                            f"verdict (first {wrong[:8]})")
        counters = check_batcher(service.batcher, rows_sent=n_tx)
    finally:
        service.shutdown()
    since_warm = get_profiler().compiles_since_warm()
    _require(since_warm == 0,
             f"{since_warm} compile(s) after mark_warm(): the phase's shape "
             "let a second bucket in")
    return {"transactions": n_tx,
            "bad_transactions": sum((i % distinct) in bad
                                    for i in range(n_tx)),
            **counters, "compiles_since_warm": since_warm}


#: the ledger phase's window: a few seconds of the steady cell's offered load
LEDGER_SECONDS = 5.0


def phase_ledger(seed: int, seconds: float = LEDGER_SECONDS,
                 scale: dict | None = None) -> dict:
    """The served path as the benchmark's cells run it: ``seconds`` of
    ``crosscash-raft.steady`` (24 parties, a validating notary over 3 durable
    raft replicas, one shared verifier at default routing) through the
    benchmark's own driver, which judges the guarantees against its plain
    references. ``scale`` is the tiny-size override of the CPU rehearsal."""
    bench = str(ROOT / "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import run as bench_run  # benchmarks/run.py

    cell = bench_run.Cell("crosscash-raft.steady")
    ctx = bench_run.RunContext(cell, seed, seconds, False, scale=scale,
                               quiet=True)
    try:
        out = bench_run.load_module("drivers", cell.driver_name).run(ctx)
    finally:
        ctx.cleanup()
    checks = {c["check"]: c for c in ctx.checks}
    failed = [c for c in ctx.checks if not c["ok"]]
    _require(ctx.correct, f"the driver's checks failed: {failed}")
    committed = out["attempted"] - out["failed"]
    _require(committed > 0, "no operation committed")
    (counters,) = [n for n in ctx.notes if n["note"] == "batcher"]
    return {"correct": True, "ops_committed": committed,
            "ops_total": out["attempted"],
            "commit_ms_p50": out["end_to_end"]["commit_ms_p50"],
            "exactly_once_ok": checks["exactly_once_violations"]["ok"],
            "replicas_agree": checks["replica_disagreements"]["ok"],
            **{name: counters[name] for name in
               ("DeviceChecked", "HostRouted", "BatchFailure",
                "BreakerRouted")},
            "compiles_since_warm":
                checks["compiles_after_mark_warm"]["value"],
            # finding for ROADMAP A3, not a failure: at today's thresholds
            # the served path sends every check to the host
            "finding": "served path device/host split at default routing"}


class _PlacementRecorder:
    """Wraps the kernel flight recorder to note where each sharded
    dispatch's output and replicated inputs live."""

    def __init__(self, inner):
        self._inner = inner
        self.seen: list = []

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def call(self, name, fn, *args, **kwargs):
        out = self._inner.call(name, fn, *args, **kwargs)
        self.seen.append((name, out, args))
        return out


def phase_mesh(seed: int, rows: int = ROWS, unique: int = UNIQUE,
               n_chips: int = 4, corrupt_every: int = CORRUPT_EVERY,
               leaves: int = 4096, service=None) -> dict:
    """What exists only across chips: the dp-sharded Ed25519 verify through
    the service seam against the host reference, and the sharded Merkle
    root (the path with the all_gather) against hashlib."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from corda_tpu.observability import get_profiler
    from corda_tpu.observability.profiling import set_profiler
    from corda_tpu.ops import sha256 as sha_ops
    from corda_tpu.parallel import make_mesh, sharded_merkle_root
    from corda_tpu.parallel.sharded import AXIS
    from corda_tpu.utils.metrics import MetricRegistry
    from corda_tpu.verifier.service import TpuTransactionVerifierService

    mesh = make_mesh(n_chips)
    if service is None:
        service = TpuTransactionVerifierService(metrics=MetricRegistry(),
                                                mesh=mesh)
    _require(service.batcher.mesh is not None
             and service.batcher.mesh.devices.size == n_chips,
             "the service's batcher holds no mesh of the asked size")
    prof = get_profiler()
    recorder = _PlacementRecorder(prof)
    set_profiler(recorder)
    try:
        checks, corrupted = signed_rows("ed25519", rows, unique, seed,
                                        corrupt_every)
        got = service.batcher.submit_group(checks).result(
            timeout=FUTURE_TIMEOUT_S)
        compare_verdicts("ed25519", got, checks, corrupted)
        counters = check_batcher(service.batcher, rows_sent=rows)
    finally:
        set_profiler(prof)
        service.shutdown()
    sharded = [(out, args) for name, out, args in recorder.seen
               if name == "sharded.ed25519"]
    _require(sharded, "no sharded.ed25519 dispatch was recorded")
    for out, args in sharded:
        check_placement("sharded.ed25519 verdicts", out, n_chips)
        for tab in args[-6:]:
            check_placement("sharded.ed25519 replicated table", tab, n_chips)

    leaf_bytes = [hashlib.sha256(b"leaf/%d/%d" % (seed, i)).digest()
                  for i in range(leaves)]
    placed = jax.device_put(sha_ops.digests_from_bytes(leaf_bytes),
                            NamedSharding(mesh, P(AXIS, None)))
    check_placement("sharded_merkle_root leaves", placed, n_chips)
    root = get_profiler().call("sharded.merkle_root",
                               sharded_merkle_root(mesh), placed)
    got_root = sha_ops.digests_to_bytes(np.asarray(root)[None])[0]
    _require(got_root == merkle_root_hashlib(leaf_bytes),
             "sharded Merkle root differs from hashlib's")
    return {"chips": n_chips, "rows": rows,
            "corrupted_rows": len(corrupted),
            "shard_devices": sorted(str(d) for d in
                                    devices_of(sharded[0][0])),
            "merkle_leaves": leaves, **counters, **compile_report()}


# -- driver ----------------------------------------------------------------------

def run_phases(phases) -> dict | None:
    """Run (name, fn) in order, one JSON line each; None at the first
    failure (its reason is on that phase's line)."""
    device = None
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fields = fn()
        except Exception as e:
            reason = str(e) if isinstance(e, PhaseFailed) else repr(e)
            print(json.dumps({"phase": name, "ok": False,
                              "seconds": round(time.perf_counter() - t0, 1),
                              "error": reason[:2000]}), flush=True)
            return None
        print(json.dumps({"phase": name, "ok": True,
                          "seconds": round(time.perf_counter() - t0, 1),
                          **fields}), flush=True)
        if name == "device":
            device = {k: fields[k] for k in ("platform", "kind", "count")}
    return device


def _give_up() -> None:
    print(json.dumps({"phase": "deadline", "ok": False,
                      "error": f"not finished after {DEADLINE_S} s"}),
          flush=True)
    os._exit(1)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only device, native and the mesh phase")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)
    if args.chips == 4:
        phases = [("device", lambda: phase_device(min_count=4)),
                  ("native", phase_native),
                  ("mesh", lambda: phase_mesh(args.seed))]
    else:
        phases = [("device", phase_device),
                  ("native", phase_native),
                  ("kernels", lambda: phase_kernels(args.seed)),
                  ("service", lambda: phase_service(args.seed)),
                  ("ledger", lambda: phase_ledger(args.seed))]
    watchdog = threading.Timer(DEADLINE_S, _give_up)
    watchdog.daemon = True
    watchdog.start()
    t0 = time.perf_counter()
    device = run_phases(phases)
    if device is None:
        return 1
    print(json.dumps({"phase": "total", "ok": True,
                      "seconds": round(time.perf_counter() - t0, 1)}),
          flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
