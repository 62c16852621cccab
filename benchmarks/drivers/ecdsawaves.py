"""Driver ``ecdsawaves``: ``sigwaves``' closed loop for an ECDSA deployment.
Closed-loop clients hand one verifier whole waves of signatures, one
``submit_group`` per wave, and wait for every verdict.

What differs from ``sigwaves`` comes from the configuration: the scheme
(``schemes``: one name of ``corda_tpu.core.crypto.schemes`` by its curve),
the pool (``ecdsa_pool``: compressed SEC1 party keys, DER signatures as the
reference's signer emits them, about half with a high ``s``, five kinds of
corrupted rows) and the plain reference (``reference``). Set-up first hands
``Crypto.is_valid`` one high-s signature of that signer: a program that
refuses it cannot run the deployment, and the run ends there with
``BenchError`` (exit 2), not 200 s later with half of every wave refused.
Then, as ``sigwaves``: ONE ``TpuTransactionVerifierService`` whose batcher
takes ``batcher_args`` and nothing else, one wave alone and one round of the
closed loop unmeasured, ``mark_warm()``, the window, and every completed wave
compared row for row with the reference.

On top of ``sigwaves``' checks: no row was prepared by the batcher's
item-form fallback (``SigBatcher.EcdsaItemsPrep``, the pure-Python prep the
program takes in silence when ``libscalarmath.so`` is missing or stale), and
the rows the prep refused before the kernel (``SigBatcher.EcdsaRefusedEncoding``
+ ``EcdsaRefusedRange``) are the pool's rows with a padded DER integer or
with ``s + n``, and no other.

``sigs_per_s`` is ``sigwaves``' own: the verdicts returned inside the window
over the clock's window. The ``window`` line also says when the last of them
returned (``last_verdict_s``), as a note and no more.

Controls (``--control``), each of which has to come out ``correct: false``:
``unchecked_rows`` puts the reference in the verifier's place with every other
row waved through.

A later ``benchmark`` issue folds this file and ``sigwaves.py`` into one
(ROADMAP A7): a PR that adds a deployment may edit no file that is here.
"""
from __future__ import annotations

import importlib
import pathlib
import sys
import threading
import time

import numpy as np

import ecdsa_pool
from bench_common import check_device_path, nearest_rank
from drivers.sigwaves import pool_digest

SCHEMES = {"secp256k1": "ECDSA_SECP256K1_SHA256",
           "secp256r1": "ECDSA_SECP256R1_SHA256"}


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
    from corda_tpu.core.crypto.signatures import Crypto
    from corda_tpu.observability import (disable_tracing, enable_tracing,
                                         get_profiler, get_tracer)
    from corda_tpu.utils.metrics import MetricRegistry
    from corda_tpu.verifier.batcher import SignatureBatcher
    from corda_tpu.verifier.service import TpuTransactionVerifierService

    p = ctx.param
    (curve,) = p("schemes")
    scheme = getattr(schemes, SCHEMES[curve])
    ref = load_reference(ctx)
    clients = int(p("clients"))
    wave_size = int(p("wave_size"))
    n_waves = int(p("pool_waves"))
    timeout = float(p("wave_timeout_s", 1100.0))
    try:
        pub, sig, msg = high_s_probe(curve)
    except ecdsa_pool.SignerUnavailable as e:
        raise bench_error(ctx, str(e))
    if not Crypto.is_valid(PublicKey(scheme, pub), sig, msg):
        raise bench_error(
            ctx, f"the program refuses a valid {curve} signature with "
            f"s > n/2, which the deployment's signer emits for half its "
            f"rows: {ctx.cell.config['name']} is not a deployment it can run")
    if ctx.trace:
        enable_tracing(int(p("trace_capacity", 65536)))
    raw_pool, corrupted = ecdsa_pool.build_pool(
        ctx.seed, n_waves, wave_size, int(p("party_keys")),
        int(p("corrupt_every")), curve)
    key_of: dict = {}
    pool = [[(key_of.setdefault(pub, PublicKey(scheme, pub)), sig, msg)
             for pub, sig, msg in rows] for rows in raw_pool]
    ctx.say("pool", scheme=curve, waves=n_waves, wave_size=wave_size,
            corrupted_per_wave=len(corrupted[0]),
            high_s_share=ecdsa_pool.high_s_share(raw_pool, curve),
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
        raise ValueError(f"driver ecdsawaves has no control {ctx.control!r}")
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
        # side), then one round of the cell's own closed loop
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
        t_open, wall_open = time.perf_counter(), time.time()
        threads = run_clients(None)
        time.sleep(ctx.seconds)
        t_close, wall_close = time.perf_counter(), time.time()
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
                last_verdict_s=max((d[2] for d in inside), default=t_open)
                - t_open,
                wave_ms_p50=nearest_rank(wave_s, 0.5) * 1e3,
                wave_ms_max=wave_s[-1] * 1e3 if wave_s else None)

        # every verdict of every wave against the plain reference
        t_ref = time.perf_counter()
        valid = ecdsa_pool.parallel_map(f"{ref.__name__}:verdicts", raw_pool,
                                        n_waves * wave_size)
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
        prep = {n: registry.meter(f"SigBatcher.{n}").count
                for n in ("EcdsaWordsPrep", "EcdsaItemsPrep",
                          "EcdsaRefusedEncoding", "EcdsaRefusedRange")}
        if ctx.control is None:
            ctx.check("batches_not_of_the_pinned_size",
                      abs(rows_batched - n_batches * want_size)
                      + max(0.0, sizes1[2] - want_size), 0)
            ctx.check("host_routed_rows", b["HostRouted"],
                      int(p("host_routed_limit", 0)))
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
        attempted = len(window)
        return {"attempted": attempted, "failed": len(errors),
                "end_to_end": e2e,
                "layer_data": {"snap0": snap0, "snap1": snap1, "spans": spans,
                               "samples": {"wave_s": wave_s},
                               # the span readers take the window's spans
                               "window_wall": (wall_open, wall_close),
                               "gap_prefixes": ("batcher.",)}}
    finally:
        stop.set()
        service.shutdown()
        if ctx.trace:
            disable_tracing()
