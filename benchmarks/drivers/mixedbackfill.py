"""Driver ``mixedbackfill``: a node whose verifier is the IN-PROCESS
``TpuTransactionVerifierService`` is handed a mixed-scheme generated ledger,
whole ``SignedTransaction``s, a ``VerifyMany`` wave at a time.

One process. The service is ``make_verifier_service("Tpu")`` with a
``SignatureBatcher`` built from the configuration's ``batcher_args`` and
nothing else. The pool is ``ledgers`` generated ledgers
(``mixed_ledgers.make_ledger``, in parallel processes): Ed25519 and secp256k1
parties, CompositeKey owners, a 1-of-3 cluster notary, some transactions
altered. Each ledger is recorded in a ``MockServices`` of its own, so every
input resolves node-side, and is ONE WAVE: a client hands it, in ledger order,
to ``verify_wave`` (the entry point ``_do_verify_many`` calls), waits for
every member's verdict, and takes the next ledger of the pool.

A program whose service has no ``verify_wave`` (any parent of PR 42) cannot
run the deployment: the run ends with ``BenchError`` (exit 2) on set-up's
first call, before a ledger is made or a kernel loaded.

Set-up: the ledgers; one ``submit_group`` of exactly each rung of the
``bucket_ladder`` for EACH scheme (a rung at a time, the two schemes side by
side), so that every shape a device flush can take is compiled and run
(``mark_warm()`` after); the heap as it stands collected once and frozen, and
the collector set to the configuration's ``collector_thresholds`` (its
``assumed`` has the reason) until the run ends; the closed loop started and
run, unmeasured, until ``warm_verdicts`` verdicts are back. The window opens on the running loop:
``--seconds`` on this process's clock; then the clients finish the wave they
are in. ``tx_per_s`` is ``bench_common.window_rate``'s with ONE TRANSACTION'S
VERDICT as the unit: the verdicts a client read inside the window over the
window's length; a verdict read after the close counts for nothing.

``correct`` (every limit 0 unless said): every verdict of the run (warm-up,
window, drain), by CLASS (valid / a signature does not verify / signatures
missing / anything else), against the plain reference's for that
transaction; the reference against the set of altered transactions; every
altered kind seen and judged for its own reason; every wave member answered
exactly once (a future a member, each read once, none left pending); at most
``host_routed_limit`` (a share) of the window's signature rows host-routed;
every wave of the run admitted in bulk; no device flush at a padded row
count that set-up did not run; no row prepared by either item-form fallback
(``SigBatcher.EcdsaItemsPrep`` = ``Ed25519ItemsPrep`` = 0); and
``bench_common.check_device_path``.

Controls (``--control``), each of which has to come out ``correct: false``:
``unchecked_rows`` gives the service a stand-in batcher that calls every
signature valid; ``thresholds_ignored`` makes coverage treat a CompositeKey as
fulfilled by any one of its leaves.
"""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

import ecdsa_pool
import mixed_ledgers
from bench_common import GcWatch, check_device_path, window_rate
from drivers import oopstream
from drivers.oopstream import (FIRST_CALL_TIMEOUT_S, PADDED, meter_delta,
                               padded_counts)
from drivers.sigwaves import bench_error, load_reference

#: a wave's verdicts
WAVE_TIMEOUT_S = 600.0
CLASSES = (mixed_ledgers.VALID, mixed_ledgers.BAD_SIGNATURE,
           mixed_ledgers.MISSING, "other")
BUCKETS = {"ed25519": "EDDSA_ED25519_SHA512",
           "secp256k1": "ECDSA_SECP256K1_SHA256"}


class UncheckedBatcher(oopstream.UncheckedBatcher):
    """CONTROL, never the program: the oop cell's stand-in (every signature
    waved through) with the one more method the service asks of a batcher."""

    def wave_is_the_planners(self, signers, wave_rows):
        return True


def any_leaf_fulfils(self, keys, tally=None) -> bool:
    """CONTROL, never the program: a CompositeKey fulfilled by any one of
    its leaves, whatever its thresholds and weights."""
    return not self.keys.isdisjoint(keys)


def run(ctx) -> dict:
    from corda_tpu.verifier.service import make_verifier_service
    if ctx.control not in (None, "unchecked_rows", "thresholds_ignored"):
        raise ValueError(f"driver mixedbackfill has no control "
                         f"{ctx.control!r}")
    from corda_tpu.utils.metrics import MetricRegistry
    from corda_tpu.verifier.batcher import SignatureBatcher
    from corda_tpu.verifier.service import TpuTransactionVerifierService
    if not hasattr(TpuTransactionVerifierService, "verify_wave"):
        raise bench_error(
            ctx, "the program's TpuTransactionVerifierService has no wave "
            "entry point (verify_wave): it cannot be handed a VerifyMany "
            "wave as a wave, so genledger-mixed is not a deployment it can "
            "run")
    import corda_tpu.core.transactions  # noqa: F401  (the wire types,
    import corda_tpu.testing.dummy  # noqa: F401      and the ledger's)
    from corda_tpu.core.crypto import schemes
    from corda_tpu.core.crypto.composite import CompositeKey
    from corda_tpu.core.crypto.signatures import SignatureException
    from corda_tpu.core.serialization import deserialize
    from corda_tpu.core.transactions.signed import \
        SignaturesMissingException
    from corda_tpu.observability import (disable_tracing, enable_tracing,
                                         get_profiler, get_tracer)
    from corda_tpu.testing.services import MockServices

    p = ctx.param
    ref = load_reference(ctx)
    curves = list(p("schemes"))
    n_ledgers, per_ledger = int(p("pool_waves")), int(p("wave_transactions"))
    if (n_ledgers, per_ledger) != (int(p("ledgers")),
                                   int(p("ledger_transactions"))):
        raise bench_error(ctx, "a wave is one ledger: the traffic's "
                          "pool_waves x wave_transactions has to be the "
                          "configuration's ledgers x ledger_transactions")
    clients = int(p("clients"))
    invalid_every = int(p("invalid_every"))
    batcher_args = dict(p("batcher_args"))
    ladder = sorted(int(r) for r in batcher_args["bucket_ladder"])
    shipped = gc.get_threshold()
    collector = p("collector_thresholds")
    if ctx.trace:
        enable_tracing(int(p("trace_capacity", 262144)))

    # -- the ledgers, made side by side ---------------------------------------
    t0 = time.perf_counter()
    per_invalid = per_ledger // invalid_every if invalid_every else 0
    jobs = [(s, per_ledger, int(p("party_keys")), int(p("composite_parties")),
             int(p("nested_composites")), int(p("notary_replicas")),
             invalid_every, k * per_invalid)
            for k, s in enumerate(mixed_ledgers.ledger_seeds(ctx.seed,
                                                             n_ledgers))]
    try:
        made = ecdsa_pool.parallel_map("mixed_ledgers:make_ledger", jobs,
                                       n_ledgers * per_ledger)
    except ecdsa_pool.SignerUnavailable as e:
        raise bench_error(ctx, str(e))
    facts = [f for m in made for f in m["facts"]]
    kinds = {k * per_ledger + i: kind
             for k, m in enumerate(made) for i, kind in m["kinds"].items()}
    rows_by_scheme = {c: sum(s[0] == getattr(ref, c.upper())
                             for f in facts for s in f[1]) for c in curves}
    n_rows = sum(len(f[1]) for f in facts)
    n_required = sum(len(f[2]) for f in facts)
    waves = []                  # (the ledger's transactions, its services)
    for m in made:
        services = MockServices()
        txs = [deserialize(b) for b in m["stx"]]
        services.record_transactions(*txs)      # resolves, and primes ids
        waves.append((txs, services))
    ctx.say("ledgers", ledgers=n_ledgers, transactions=len(facts),
            signatures=n_rows, signatures_per_tx=n_rows / len(facts),
            rows_by_scheme=rows_by_scheme,
            k1_row_share=rows_by_scheme.get("secp256k1", 0) / max(1, n_rows),
            required_keys=n_required,
            composite_required_share=sum(m["composite_required"]
                                         for m in made) / max(1, n_required),
            altered=len(kinds), seconds=time.perf_counter() - t0)
    del made

    registry = MetricRegistry()
    if ctx.control == "unchecked_rows":
        batcher = UncheckedBatcher(registry, batcher_args["max_batch"])
    else:
        batcher = SignatureBatcher(metrics=registry, **batcher_args)
    service = make_verifier_service("Tpu", metrics=registry, batcher=batcher)
    the_rule = CompositeKey.is_fulfilled_by
    if ctx.control == "thresholds_ignored":
        CompositeKey.is_fulfilled_by = any_leaf_fulfils
    gc_watch = GcWatch()
    stop = threading.Event()
    lock = threading.Lock()
    done: list = []     # (wave, t_submit, times of each verdict, classes)
    errors: list = []
    others: list = []   # the first outcomes of no known class
    counted = [0]

    def classify(exc) -> int:
        if exc is None:
            return 0
        if isinstance(exc, SignaturesMissingException):
            return 2
        if isinstance(exc, SignatureException):
            return 1
        if len(others) < 3:
            others.append(repr(exc)[:160])
        return 3

    def client(c: int) -> None:
        k = c * (n_ledgers // max(1, clients))
        try:
            while not stop.is_set():
                w = k % n_ledgers
                txs, services = waves[w]
                t_sub = time.perf_counter()
                with ctx.span("client.wave"):
                    futures = service.verify_wave(txs, services)
                    if len(futures) != len(txs):
                        raise RuntimeError(
                            f"{len(futures)} futures for {len(txs)} members")
                    times = np.empty(len(txs))
                    classes = np.empty(len(txs), dtype=np.int8)
                    for i, fut in enumerate(futures):
                        classes[i] = classify(
                            fut.exception(timeout=WAVE_TIMEOUT_S))
                        times[i] = time.perf_counter()
                with lock:
                    done.append((w, t_sub, times, classes))
                    counted[0] += len(txs)
                k += 1
        except Exception as e:      # surfaces as a failed run, not a hang
            errors.append(repr(e))
            stop.set()

    threads = []
    try:
        # -- every shape a device flush can take, each scheme's own ----------
        t0 = time.perf_counter()
        first_calls = {}
        if ctx.control != "unchecked_rows":
            rows = {}
            for curve in curves:
                scheme = getattr(schemes, BUCKETS[curve])
                rows[curve] = [(sig.by, sig.bytes, stx.id.bytes)
                               for txs, _services in waves for stx in txs
                               for sig in stx.sigs if sig.by.scheme == scheme]
                if len(rows[curve]) < ladder[-1]:
                    raise bench_error(
                        ctx, f"the pool holds {len(rows[curve])} {curve} "
                        f"rows, under the ladder's top rung {ladder[-1]}")
            # a rung at a time, the schemes side by side (two executables
            # load, or compile, at once: what the machine is known to hold)
            for rung in reversed(ladder):
                t1 = time.perf_counter()
                firsts = {c: batcher.submit_group(rows[c][:rung])
                          for c in curves}
                for curve, fut in firsts.items():
                    fut.result(timeout=FIRST_CALL_TIMEOUT_S)
                    first_calls[f"{curve}@{rung}"] = time.perf_counter() - t1
            del rows
        get_profiler().mark_warm()
        warmed = padded_counts(registry.snapshot())
        ctx.say("warm", rungs=ladder, first_call_s=first_calls,
                padded_rows_run=sorted(warmed, key=int),
                seconds=time.perf_counter() - t0)
        if ctx.control != "unchecked_rows" \
                and sorted(warmed, key=int) != [str(r) for r in ladder]:
            raise bench_error(
                ctx, f"set-up dispatched the ladder {ladder} and the "
                f"batcher's {PADDED}<rows> meters name the padded row counts "
                f"{sorted(warmed, key=int)}: the window's flushes cannot be "
                f"held to the shapes set-up ran")

        # -- the closed loop, unmeasured until it has settled -----------------
        t0 = time.perf_counter()
        gc.collect()
        gc.freeze()
        if collector is not None:
            gc.set_threshold(*collector)
        snap_loop = registry.snapshot()
        threads = [threading.Thread(target=client, args=(c,),
                                    name=f"bench-client-{c}")
                   for c in range(clients)]
        for t in threads:
            t.start()
        warm_verdicts = int(p("warm_verdicts"))
        while counted[0] < warm_verdicts and not stop.is_set():
            time.sleep(0.02)
        if errors:
            raise RuntimeError(f"warm-up failed: {errors[0]}")
        ctx.say("loop", warm_verdicts=counted[0],
                seconds=time.perf_counter() - t0)
        gc_watch.start()
        snap0 = registry.snapshot()

        ctx.window_opens()
        t_open, wall_open = time.perf_counter(), time.time()
        depths = []
        ticks = max(1, int(ctx.seconds))
        for tick in range(1, ticks + 1):
            # to the tick's own second: a late wake-up is not carried over
            time.sleep(max(0.0, t_open + tick * ctx.seconds / ticks
                           - time.perf_counter()))
            if tick < ticks:
                depths.append(batcher.queue_depths())
        t_close, wall_close = time.perf_counter(), time.time()
        collector = gc_watch.stop()
        snap1 = registry.snapshot()
        stop.set()
        for t in threads:
            t.join(timeout=WAVE_TIMEOUT_S)
        unjoined = sum(t.is_alive() for t in threads)
        ctx.trace_closes()
        snap_end = registry.snapshot()
        spans = []
        if ctx.trace:
            for trace_spans in get_tracer().traces().values():
                spans.extend(trace_spans)

        window_s = t_close - t_open
        inside = np.concatenate([times for _w, _s, times, _c in done]
                                or [np.empty(0)]) - t_open
        rate = window_rate(inside[inside >= 0.0].tolist(), window_s, 1)
        rate["tx_per_s"] = rate.pop("sigs_per_s")
        rate["verdicts_inside"] = rate.pop("waves_completed_inside")
        rate["verdicts_after"] = rate.pop("waves_finished_after")
        wave_s = sorted(float(times.max()) - t_sub
                        for _w, t_sub, times, _c in done
                        if t_open <= times.max() <= t_close)
        ctx.say("window", **rate, waves_inside=len(wave_s),
                wave_ms_p50=wave_s[len(wave_s) // 2] * 1e3 if wave_s else None,
                wave_ms_max=wave_s[-1] * 1e3 if wave_s else None,
                **collector)
        if ctx.trace:
            ctx.say("spans", recorded=len(spans),
                    capacity=int(p("trace_capacity", 262144)),
                    waves=sum(s.get("name") == "verifier.wave"
                              for s in spans))
        ctx.say("queue_depths_each_second", rows=depths)

        # -- every verdict against the plain reference ------------------------
        t_ref = time.perf_counter()
        chunk = -(-len(facts) // max(1, n_ledgers))
        want_names = [v for part in ecdsa_pool.parallel_map(
            f"{ref.__name__}:verdicts",
            [facts[i:i + chunk] for i in range(0, len(facts), chunk)],
            len(facts)) for v in part]
        known = sum(v != (mixed_ledgers.VERDICTS[kinds[i]] if i in kinds
                          else mixed_ledgers.VALID)
                    for i, v in enumerate(want_names))
        want = np.array([CLASSES.index(v) if v in CLASSES else 3
                         for v in want_names], dtype=np.int8)
        differing, compared, judged = [], 0, {}
        for w, _s, _times, classes in done:
            mine = want[w * per_ledger:(w + 1) * per_ledger]
            compared += len(classes)
            for i in np.nonzero(classes != mine)[0][:3]:
                differing.append((w * per_ledger + int(i),
                                  CLASSES[classes[i]], CLASSES[mine[i]]))
            differing_n = int((classes != mine).sum())
            judged["differing"] = judged.get("differing", 0) + differing_n
            for i, kind in kinds.items():
                if i // per_ledger == w \
                        and classes[i % per_ledger] == mine[i % per_ledger]:
                    judged[kind] = judged.get(kind, 0) + 1
        n_differing = judged.pop("differing", 0)
        ctx.say("reference", transactions=len(want), verdicts_compared=compared,
                altered_judged_by_kind=judged, first_differing=differing[:3],
                outcomes_of_no_known_class=others,
                seconds=time.perf_counter() - t_ref)
        handed = meter_delta(snap_loop, snap_end, "Verifier.WaveTx.bulk") \
            + meter_delta(snap_loop, snap_end, "Verifier.WaveTx.held")
        ctx.check("client_errors", len(errors) + unjoined, 0)
        ctx.check("verdicts_inside_window_missing",
                  int(rate["verdicts_inside"] == 0), 0)
        ctx.check("reference_disagrees_with_altered_set", known, 0)
        ctx.check("verdicts_differing_from_reference", n_differing, 0)
        ctx.check("altered_kinds_never_judged_for_their_own_reason",
                  len(set(kinds.values()) - set(judged)), 0)
        ctx.check("altered_kinds_missing_from_the_pool",
                  len(mixed_ledgers.KINDS) - len(set(kinds.values())), 0)
        ctx.check("members_not_answered_exactly_once",
                  abs(handed - compared), 0)
        ctx.check("members_of_waves_not_admitted_in_bulk",
                  meter_delta(snap_loop, snap_end, "Verifier.WaveTx.held"), 0)
        if ctx.control != "unchecked_rows":
            b = check_device_path(ctx, registry, batcher)
            dev = meter_delta(snap0, snap1, "SigBatcher.DeviceChecked")
            hst = meter_delta(snap0, snap1, "SigBatcher.HostRouted")
            ctx.check("host_routed_share_of_the_window",
                      hst / max(1, dev + hst),
                      float(p("host_routed_limit")))
            ctx.check("device_rows_of_the_window_missing", int(dev == 0), 0)
            padded = padded_counts(snap_end)
            fresh = {rows: n for rows, n in padded.items()
                     if n and rows not in warmed}
            ctx.check("device_flushes_at_padded_rows_set_up_did_not_run",
                      sum(fresh.values()) if padded else -1, 0,
                      ok=bool(padded) and not fresh)
            ctx.check("rows_prepared_by_an_item_form_fallback",
                      meter_delta({}, snap_end, "SigBatcher.EcdsaItemsPrep")
                      + meter_delta({}, snap_end,
                                    "SigBatcher.Ed25519ItemsPrep"), 0)
            ctx.say("batcher", **b, device_rows_in_window=dev,
                    host_rows_in_window=hst,
                    device_rows_by_bucket={
                        c: meter_delta(snap0, snap1,
                                       f"SigBatcher.DeviceChecked.{c}")
                        for c in curves},
                    flushes_by_padded_rows=padded,
                    flushes_by_reason={
                        n.rsplit(".", 1)[1]: meter_delta(snap0, snap1, n)
                        for n in snap1
                        if n.startswith("SigBatcher.DeviceFlush.")})
        return {"attempted": compared,
                "failed": len(errors) + unjoined,
                "end_to_end": {"tx_per_s": rate["tx_per_s"]},
                "layer_data": {"snap0": snap0, "snap1": snap1,
                               "spans": spans,
                               "window_wall": (wall_open, wall_close),
                               "trace_wall_t0": ctx.trace_segments[0][1]
                               if ctx.trace_segments else None,
                               "gap_prefixes": ("batcher.", "verifier.")}}
    finally:
        stop.set()
        gc_watch.stop()
        gc.set_threshold(*shipped)
        gc.unfreeze()
        CompositeKey.is_fulfilled_by = the_rule
        for t in threads:
            t.join(timeout=30)
        service.shutdown()
        if ctx.trace:
            disable_tracing()
