"""Driver ``sigwaves``: closed-loop clients hand one verifier whole waves of
signatures, one ``submit_group`` per wave, and wait for every verdict.

What differs from one signature deployment to the next comes from its
configuration: the scheme (``schemes``, one name of ``SCHEMES``), the seeded
pool and the plain reference (``reference``). An Ed25519 pool is built here:
signed with the ``cryptography`` package (same bytes as the program's own
signer by RFC 8032, which is pure Python at milliseconds a signature), 1 row
in ``corrupt_every`` corrupted at seeded indices (flipped signature byte,
another signer's key, altered message). An ECDSA pool is ``ecdsa_pool``'s:
compressed SEC1 party keys, DER signatures as the reference's signer emits
them, about half with a high ``s``, five kinds of corrupted rows; for such a
deployment set-up first hands ``Crypto.is_valid`` one high-s signature of
that signer: a program that refuses it cannot run the deployment, and the run
ends there with ``BenchError`` (exit 2), not 200 s later with half of every
wave refused.

Set-up then builds ONE ``TpuTransactionVerifierService`` whose batcher takes
``batcher_args`` and nothing else, and runs one wave alone and then one round
of the closed loop, unmeasured, so that every shape the window reaches has
been seen (and is compiled once, not once per prep worker); then
``mark_warm()``, and what set-up left on the heap (the pool: 65,536 rows, their
keys) leaves the collector's reach (``gc.freeze()``), as ``latejoin`` does.
The window runs ``clients`` threads for ``--seconds``. ``sigs_per_s`` is
``bench_common.window_rate``'s, the one rule of every wave cell: the verdicts
returned inside the window over the clock's window; verdicts that return
after it closes count for nothing. Afterwards every completed wave is
compared row for row with the plain reference.

On top of that an ECDSA deployment checks that no row was prepared by the
batcher's item-form fallback (``SigBatcher.EcdsaItemsPrep``, the pure-Python
prep the program takes in silence when ``libscalarmath.so`` is missing or
stale), and that the rows the prep refused before the kernel
(``SigBatcher.EcdsaRefusedEncoding`` + ``EcdsaRefusedRange``) are the pool's
rows with a padded DER integer or with ``s + n``, and no other.

Controls (``--control``), each of which has to come out ``correct: false``:
``unchecked_rows`` puts the reference in the verifier's place with every other
row waved through.
"""
from __future__ import annotations

import gc
import hashlib
import importlib
import pathlib
import sys
import threading
import time

import numpy as np

import ecdsa_pool
from bench_common import (GcWatch, check_device_path, nearest_rank,
                          window_rate)

#: a configuration's scheme -> its name in ``corda_tpu.core.crypto.schemes``
SCHEMES = {"ed25519": "EDDSA_ED25519_SHA512",
           "secp256k1": "ECDSA_SECP256K1_SHA256",
           "secp256r1": "ECDSA_SECP256R1_SHA256"}
ECDSA_METERS = ("EcdsaWordsPrep", "EcdsaItemsPrep", "EcdsaRefusedEncoding",
                "EcdsaRefusedRange")


def build_pool(seed: int, waves: int, wave_size: int, n_keys: int,
               corrupt_every: int):
    """The Ed25519 pool: ``waves`` lists of ``wave_size`` (raw public key,
    signature, message) rows, and per wave the set of corrupted row indices.
    Keys repeat as on a ledger (``n_keys`` parties); every message is a fresh
    32-byte id."""
    from cryptography.hazmat.primitives import serialization
    from cryptography.hazmat.primitives.asymmetric.ed25519 import \
        Ed25519PrivateKey
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0x51C5])
    raw = serialization.Encoding.Raw, serialization.PublicFormat.Raw
    keys = []
    for _ in range(n_keys):
        sk = Ed25519PrivateKey.from_private_bytes(rng.bytes(32))
        keys.append((sk, sk.public_key().public_bytes(*raw)))
    pool, corrupted = [], []
    for _w in range(waves):
        signer = rng.integers(0, n_keys, size=wave_size)
        msgs = rng.bytes(32 * wave_size)
        n_bad = wave_size // corrupt_every
        bad_rows = rng.choice(wave_size, size=n_bad, replace=False)
        bad = {int(r): k % 3 for k, r in enumerate(sorted(bad_rows))}
        rows = []
        for i in range(wave_size):
            sk, pub = keys[signer[i]]
            msg = msgs[32 * i:32 * i + 32]
            sig = sk.sign(msg)
            kind = bad.get(i)
            if kind == 0:
                sig = sig[:-1] + bytes([sig[-1] ^ 1])
            elif kind == 1:
                pub = keys[(signer[i] + 1) % n_keys][1]
            elif kind == 2:
                msg = msg[:-1] + bytes([msg[-1] ^ 1])
            rows.append((pub, sig, msg))
        pool.append(rows)
        corrupted.append(set(bad))
    return pool, corrupted


def pool_digest(pool) -> str:
    h = hashlib.sha256()
    for rows in pool:
        for pub, sig, msg in rows:
            h.update(pub + sig + msg)
    return h.hexdigest()


def bench_error(ctx, message: str) -> Exception:
    """The harness's ``BenchError`` (exit 2), from the module ``ctx`` is
    of: the driver cannot import ``run``, which runs as ``__main__``."""
    return sys.modules[type(ctx).__module__].BenchError(message)


def load_reference(ctx):
    """The configuration's plain reference (``reference/<name>.py``)."""
    stem = pathlib.PurePosixPath(ctx.param("reference")).stem
    return importlib.import_module(f"reference.{stem}")


def high_s_probe(scheme: str):
    """(compressed key, DER signature, message) by the deployment's signer,
    with ``s > n / 2``: the first such among fixed messages of a fixed key."""
    from cryptography.hazmat.primitives.asymmetric.utils import \
        decode_dss_signature
    (pub,) = ecdsa_pool.public_keys(scheme, [7])
    msgs = [i.to_bytes(32, "big") for i in range(64)]
    sigs = ecdsa_pool.sign_rows((scheme, [7], [0] * len(msgs), msgs))
    for sig, msg in zip(sigs, msgs):
        if decode_dss_signature(sig)[1] > ecdsa_pool.ORDERS[scheme] // 2:
            return pub, sig, msg
    raise AssertionError("no high-s signature in 64 tries")


def refuse_unless_high_s_is_valid(ctx, curve: str, scheme) -> None:
    from corda_tpu.core.crypto.keys import PublicKey
    from corda_tpu.core.crypto.signatures import Crypto
    try:
        pub, sig, msg = high_s_probe(curve)
    except ecdsa_pool.SignerUnavailable as e:
        raise bench_error(ctx, str(e))
    if not Crypto.is_valid(PublicKey(scheme, pub), sig, msg):
        raise bench_error(
            ctx, f"the program refuses a valid {curve} signature with "
            f"s > n/2, which the deployment's signer emits for half its "
            f"rows: {ctx.cell.config['name']} is not a deployment it can run")


class ReferenceVerifier:
    """The control's stand-in for the service: same ``submit_group``
    surface, verdicts from ``ref.control_verdicts`` on the caller's thread."""

    def __init__(self, ref, raw_pool):
        self.ref = ref
        self.raw = {id(w): r for w, r in raw_pool}

    def submit_group(self, checks):
        from concurrent.futures import Future
        fut: Future = Future()
        fut.set_result(self.ref.control_verdicts(self.raw[id(checks)]))
        return fut


def run(ctx) -> dict:
    from corda_tpu.core.crypto import schemes
    from corda_tpu.core.crypto.keys import PublicKey
    from corda_tpu.observability import (disable_tracing, enable_tracing,
                                         get_profiler, get_tracer)
    from corda_tpu.utils.metrics import MetricRegistry
    from corda_tpu.verifier.batcher import SignatureBatcher
    from corda_tpu.verifier.service import TpuTransactionVerifierService

    p = ctx.param
    (curve,) = p("schemes")
    scheme = getattr(schemes, SCHEMES[curve])
    ecdsa = curve in ecdsa_pool.ORDERS
    ref = load_reference(ctx)
    clients = int(p("clients"))
    wave_size = int(p("wave_size"))
    n_waves = int(p("pool_waves"))
    timeout = float(p("wave_timeout_s", 1100.0))
    if ecdsa:
        refuse_unless_high_s_is_valid(ctx, curve, scheme)
    if ctx.trace:
        enable_tracing(int(p("trace_capacity", 65536)))
    pool_args = (ctx.seed, n_waves, wave_size, int(p("party_keys")),
                 int(p("corrupt_every")))
    raw_pool, corrupted = ecdsa_pool.build_pool(*pool_args, curve) if ecdsa \
        else build_pool(*pool_args)
    key_of: dict = {}
    pool = [[(key_of.setdefault(pub, PublicKey(scheme, pub)), sig, msg)
             for pub, sig, msg in rows] for rows in raw_pool]
    about = {"high_s_share": ecdsa_pool.high_s_share(raw_pool, curve)} \
        if ecdsa else {}
    ctx.say("pool", scheme=curve, waves=n_waves, wave_size=wave_size,
            corrupted_per_wave=len(corrupted[0]), **about,
            digest=pool_digest(raw_pool)[:16])
    registry = MetricRegistry()
    service = TpuTransactionVerifierService(
        metrics=registry,
        batcher=SignatureBatcher(metrics=registry,
                                 **dict(p("batcher_args"))))
    if ctx.control == "unchecked_rows":
        target = ReferenceVerifier(ref, list(zip(pool, raw_pool)))
    elif ctx.control is None:
        target = service.batcher
    else:
        raise ValueError(f"driver sigwaves has no control {ctx.control!r}")
    # each client walks the pool from its own seeded offset: every seed and
    # every client sends the same waves, in another order
    order = np.random.default_rng([ctx.seed & 0xFFFFFFFF, 0xA11]) \
        .permutation(n_waves)
    done: list = []              # (wave index, t_submit, t_done, packed bits)
    lock = threading.Lock()
    stop = threading.Event()
    errors: list = []
    gc_watch = GcWatch()

    def client(c: int, rounds: int | None) -> None:
        k = c * (n_waves // max(1, clients))
        n = 0
        try:
            while not stop.is_set() and (rounds is None or n < rounds):
                w = int(order[k % n_waves])
                t_sub = time.perf_counter()
                with ctx.span("client.wave"):
                    got = target.submit_group(pool[w]).result(timeout=timeout)
                t_done = time.perf_counter()
                bits = np.packbits(np.asarray(got, dtype=bool))
                with lock:
                    done.append((w, t_sub, t_done, bits))
                k += 1
                n += 1
        except Exception as e:      # surfaces as a failed run, not a hang
            errors.append(repr(e))
            stop.set()

    def run_clients(rounds, n=clients):
        threads = [threading.Thread(target=client, args=(c, rounds),
                                    name=f"bench-client-{c}")
                   for c in range(n)]
        for t in threads:
            t.start()
        return threads

    try:
        # warm-up, unmeasured: one wave alone (on a cold cache the batcher's
        # prep workers would otherwise each compile the same kernel side by
        # side, three times the compile), then one round of the cell's own
        # closed loop
        for n in (1, clients):
            for t in run_clients(1, n):
                t.join()
        if errors:
            raise RuntimeError(f"warm-up failed: {errors[0]}")
        get_profiler().mark_warm()
        warm_waves = len(done)
        snap0 = registry.snapshot()
        size_hist = registry.histogram("verifier_batch_size")
        sizes0 = (size_hist.count, size_hist.total)
        # the pool and the reference's rows live through the whole window:
        # out of the collector's reach, so that a full collection walks the
        # window's own garbage and not 65,536 rows each time
        gc.collect()
        gc.freeze()
        gc_watch.start()

        ctx.window_opens()
        t_open, wall_open = time.perf_counter(), time.time()
        threads = run_clients(None)
        time.sleep(ctx.seconds)
        t_close, wall_close = time.perf_counter(), time.time()
        collector = gc_watch.stop()
        snap1 = registry.snapshot()
        sizes1 = (size_hist.count, size_hist.total, size_hist.max_value)
        stop.set()
        for t in threads:
            t.join(timeout=timeout)
        ctx.trace_closes()
        spans = []
        if ctx.trace:
            for trace_spans in get_tracer().traces().values():
                spans.extend(trace_spans)

        window = done[warm_waves:]
        rate = window_rate([d[2] - t_open for d in window], t_close - t_open,
                           wave_size)
        wave_s = sorted(d[2] - d[1] for d in window if d[2] <= t_close)
        ctx.say("window", **rate,
                wave_ms_p50=nearest_rank(wave_s, 0.5) * 1e3,
                wave_ms_max=wave_s[-1] * 1e3 if wave_s else None,
                **collector)

        # every verdict of every wave against the plain reference
        t_ref = time.perf_counter()
        valid = ecdsa_pool.parallel_map(f"{ref.__name__}:verdicts", raw_pool,
                                        n_waves * wave_size) if ecdsa \
            else [ref.verdicts(rows) for rows in raw_pool]
        known = sum(ok == (i in corrupted[w])
                    for w, oks in enumerate(valid) for i, ok in enumerate(oks))
        want = [np.packbits(np.asarray(oks, dtype=bool)) for oks in valid]
        mismatched = sum(
            int(np.unpackbits(bits ^ want[w])[:wave_size].sum())
            for w, _s, _d, bits in done)
        ctx.say("reference", rows=n_waves * wave_size,
                seconds=time.perf_counter() - t_ref,
                waves_compared=len(done))
        ctx.check("client_errors", len(errors), 0)
        ctx.check("waves_completed_inside_window_missing",
                  int(rate["waves_completed_inside"] == 0), 0)
        ctx.check("reference_disagrees_with_corrupted_set", known, 0)
        ctx.check("verdicts_differing_from_reference", mismatched, 0)
        n_batches = sizes1[0] - sizes0[0]
        rows_batched = sizes1[1] - sizes0[1]
        want_size = int(dict(p("batcher_args")).get("max_batch", wave_size))
        b = check_device_path(ctx, registry, service.batcher)
        prep = {n: registry.meter(f"SigBatcher.{n}").count
                for n in ECDSA_METERS} if ecdsa else {}
        if ctx.control is None:
            ctx.check("batches_not_of_the_pinned_size",
                      abs(rows_batched - n_batches * want_size)
                      + max(0.0, sizes1[2] - want_size), 0)
            ctx.check("host_routed_rows", b["HostRouted"],
                      int(p("host_routed_limit", 0)))
        if ctx.control is None and ecdsa:
            ctx.check("rows_prepared_by_the_item_form_fallback",
                      prep["EcdsaItemsPrep"], 0)
            # every wave handed over has returned by now (the clients are
            # joined), and the meters count from the service's start
            unparsable = sum(kind in ecdsa_pool.NO_WORDS for w, *_ in done
                             for kind in corrupted[w].values())
            ctx.check("rows_refused_before_the_kernel_beside_the_pools",
                      abs(prep["EcdsaRefusedEncoding"]
                          + prep["EcdsaRefusedRange"] - unparsable), 0)
        ctx.say("batcher", batches_in_window=n_batches, **b, **prep)
        return {"attempted": len(window), "failed": len(errors),
                "end_to_end": {"sigs_per_s": rate["sigs_per_s"]},
                "layer_data": {"snap0": snap0, "snap1": snap1, "spans": spans,
                               "samples": {"wave_s": wave_s},
                               # the span readers take the window's spans
                               "window_wall": (wall_open, wall_close),
                               "gap_prefixes": ("batcher.",)}}
    finally:
        stop.set()
        gc_watch.stop()
        gc.unfreeze()
        service.shutdown()
        if ctx.trace:
            disable_tracing()
