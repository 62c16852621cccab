"""Driver ``sigwaves``: closed-loop clients hand one verifier whole waves of
signatures, one ``submit_group`` per wave, and wait for every verdict.

Set-up signs a seeded pool of waves with the ``cryptography`` package (same
bytes as the program's own signer by RFC 8032, which is pure Python at
milliseconds a signature), corrupts 1 row in ``corrupt_every`` at seeded
indices (flipped signature byte, another signer's key, altered message), builds
ONE ``TpuTransactionVerifierService`` whose batcher takes ``batcher_args`` and
nothing else, and runs one wave alone and then one round of the closed loop,
unmeasured, so that every shape the window reaches has been seen (and is
compiled once, not once per prep worker); then ``mark_warm()``. The window runs
``clients`` threads for ``--seconds``; verdicts that return after it closes
count for nothing. Afterwards every completed wave is compared row for row
with the plain reference.

Controls (``--control``), each of which has to come out ``correct: false``:
``unchecked_rows`` puts the reference in the verifier's place with every other
row waved through.
"""
from __future__ import annotations

import hashlib
import threading
import time

import numpy as np

from bench_common import check_device_path, nearest_rank
from reference import genledger_ed25519 as ref  # benchmarks/reference/


def build_pool(seed: int, waves: int, wave_size: int, n_keys: int,
               corrupt_every: int):
    """``waves`` lists of ``wave_size`` (raw public key, signature, message)
    rows, and per wave the set of corrupted row indices. Keys repeat as on a
    ledger (``n_keys`` parties); every message is a fresh 32-byte id."""
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


class ReferenceVerifier:
    """The control's stand-in for the service: same ``submit_group``
    surface, verdicts from ``ref.control_verdicts`` on the caller's thread."""

    def __init__(self, raw_pool):
        self.raw = {id(w): r for w, r in raw_pool}

    def submit_group(self, checks):
        from concurrent.futures import Future
        fut: Future = Future()
        fut.set_result(ref.control_verdicts(self.raw[id(checks)]))
        return fut


def run(ctx) -> dict:
    from corda_tpu.core.crypto.keys import PublicKey
    from corda_tpu.core.crypto.schemes import EDDSA_ED25519_SHA512
    from corda_tpu.observability import (disable_tracing, enable_tracing,
                                         get_profiler, get_tracer)
    from corda_tpu.utils.metrics import MetricRegistry
    from corda_tpu.verifier.batcher import SignatureBatcher
    from corda_tpu.verifier.service import TpuTransactionVerifierService

    p = ctx.param
    clients = int(p("clients"))
    wave_size = int(p("wave_size"))
    n_waves = int(p("pool_waves"))
    timeout = float(p("wave_timeout_s", 1100.0))
    if ctx.trace:
        enable_tracing(int(p("trace_capacity", 65536)))
    raw_pool, corrupted = build_pool(ctx.seed, n_waves, wave_size,
                                     int(p("party_keys")),
                                     int(p("corrupt_every")))
    key_of: dict = {}
    pool = [[(key_of.setdefault(pub, PublicKey(EDDSA_ED25519_SHA512, pub)),
              sig, msg) for pub, sig, msg in rows] for rows in raw_pool]
    ctx.say("pool", waves=n_waves, wave_size=wave_size,
            corrupted_per_wave=len(corrupted[0]),
            digest=pool_digest(raw_pool)[:16])
    registry = MetricRegistry()
    service = TpuTransactionVerifierService(
        metrics=registry,
        batcher=SignatureBatcher(metrics=registry,
                                 **dict(p("batcher_args"))))
    if ctx.control == "unchecked_rows":
        target = ReferenceVerifier(list(zip(pool, raw_pool)))
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

        ctx.window_opens()
        t_open = time.perf_counter()
        threads = run_clients(None)
        time.sleep(ctx.seconds)
        t_close = time.perf_counter()
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
        inside = [d for d in window if d[2] <= t_close]
        wave_s = sorted(d[2] - d[1] for d in inside)
        e2e = {"sigs_per_s": len(inside) * wave_size / (t_close - t_open)}
        ctx.say("window", waves_completed_inside=len(inside),
                waves_finished_after=len(window) - len(inside),
                window_s=t_close - t_open, sigs_per_s=e2e["sigs_per_s"],
                wave_ms_p50=nearest_rank(wave_s, 0.5) * 1e3,
                wave_ms_max=wave_s[-1] * 1e3 if wave_s else None)

        # every verdict of every wave against the plain reference
        t_ref = time.perf_counter()
        valid = [ref.verdicts(rows) for rows in raw_pool]
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
                  int(len(inside) == 0), 0)
        ctx.check("reference_disagrees_with_corrupted_set", known, 0)
        ctx.check("verdicts_differing_from_reference", mismatched, 0)
        n_batches = sizes1[0] - sizes0[0]
        rows_batched = sizes1[1] - sizes0[1]
        want_size = int(dict(p("batcher_args")).get("max_batch", wave_size))
        b = check_device_path(ctx, registry, service.batcher)
        if ctx.control is None:
            ctx.check("batches_not_of_the_pinned_size",
                      abs(rows_batched - n_batches * want_size)
                      + max(0.0, sizes1[2] - want_size), 0)
            ctx.check("host_routed_rows", b["HostRouted"],
                      int(p("host_routed_limit", 0)))
        ctx.say("batcher", batches_in_window=n_batches, **b)
        attempted = len(window)
        return {"attempted": attempted, "failed": len(errors),
                "end_to_end": e2e,
                "layer_data": {"snap0": snap0, "snap1": snap1, "spans": spans,
                               "samples": {"wave_s": wave_s},
                               "gap_prefixes": ("batcher.",)}}
    finally:
        stop.set()
        service.shutdown()
        if ctx.trace:
            disable_tracing()
