"""Driver ``bookwalk``: a node whose verifier is the IN-PROCESS
``TpuTransactionVerifierService`` is handed BOOKS of the trader-demo ledger,
each one ordered ``VerifyMany``: a dependency walk's three topological levels
(cash issues and paper issues; DvP trades; redemptions), whole
``SignedTransaction``s, through ``verify_levels``.

One process. The service is ``make_verifier_service("Tpu")`` with a
``SignatureBatcher`` built from the configuration's ``batcher_args`` and
nothing else. The pool is ``books`` books (``trader_books.make_book``, in
parallel processes): secp256k1 parties, Cash and CommercialPaper states, one
member of 1 book in ``altered_every`` altered. NOTHING is recorded anywhere: a
book's services are ``ResolvedFromWalk(an empty MockServices, the book's
transactions)``, what ``_do_verify_many`` hands the verifier for a walk, so a
member's inputs resolve from the request alone. A client hands one book to
``verify_levels``, waits for its one answer ``(verified, error)`` and takes
the next book of the pool.

A program that cannot run the deployment (a service without
``verify_levels``, a ``CommercialPaper`` without ``generate_redeem``, no
``corda_tpu.testing.trader_ledger``: any parent of PR 49) ends with
``BenchError`` (exit 2) on set-up's first call, before a book is made or a
kernel loaded.

Set-up: the books; one ``submit_group`` of exactly each rung of the
``bucket_ladder`` (``mark_warm()`` after), so that every shape a device flush
can take is compiled and run; the heap as it stands collected once and
frozen, and the collector set to the configuration's ``collector_thresholds``
until the run ends; the closed loop started and run, unmeasured, until
``warm_answers`` answers are back. The window opens on the running loop:
``--seconds`` on this process's clock; then the clients finish the book they
are in. ``tx_per_s`` is the sum of ``verified`` over the answers a client
read inside the window, over the window's length: ONE TRANSACTION VERIFIED IN
ORDER is the unit, an answer read after the close counts for nothing, and a
failed book counts what passed before its altered member.

``correct`` (every limit 0 unless said): every answer of the run (warm-up,
window, drain), ``verified`` and the error's CLASS (valid / signature /
missing / contract / resolution), against the plain reference's for that
book; the reference against what the generator altered; every altered kind
seen and judged for its own reason; every request answered exactly once and
``Verification.InFlight`` back at 0; at most ``host_routed_limit`` (a share)
of the window's signature rows host-routed; every level of the run admitted
in bulk; no device flush at a padded row count that set-up did not run; no
row prepared by the item-form fallback (``SigBatcher.EcdsaItemsPrep`` = 0);
and ``bench_common.check_device_path``.

Controls (``--control``), each of which has to come out ``correct: false``:
``unchecked_rows`` gives the service a stand-in batcher that calls every
signature valid; ``rules_skipped`` makes both contracts accept everything.
"""
from __future__ import annotations

import gc
import itertools
import threading
import time

import ecdsa_pool
import trader_books
from bench_common import GcWatch, check_device_path
from drivers.mixedbackfill import UncheckedBatcher
from drivers.oopstream import (FIRST_CALL_TIMEOUT_S, PADDED, meter_delta,
                               padded_counts)
from drivers.sigwaves import bench_error, load_reference

#: a book's answer
BOOK_TIMEOUT_S = 600.0
CONTROLS = (None, "unchecked_rows", "rules_skipped")


def the_program_runs_the_deployment(ctx) -> None:
    """Refuse, before anything is built, a program that lacks what the
    deployment is made of."""
    import importlib
    from corda_tpu.finance.commercial_paper import CommercialPaper
    from corda_tpu.verifier.service import TpuTransactionVerifierService
    missing = []
    if not hasattr(TpuTransactionVerifierService, "verify_levels"):
        missing.append("TpuTransactionVerifierService.verify_levels (an "
                       "ordered VerifyMany cannot be handed over whole)")
    if not hasattr(CommercialPaper, "generate_redeem"):
        missing.append("CommercialPaper.generate_redeem (a redemption "
                       "cannot be built)")
    try:
        importlib.import_module("corda_tpu.testing.trader_ledger")
    except ImportError:
        missing.append("corda_tpu.testing.trader_ledger (no generator of a "
                       "trader-demo ledger)")
    if missing:
        raise bench_error(
            ctx, "traderdemo-replay is not a deployment this program can "
            "run: it has no " + "; no ".join(missing))


def class_of(error) -> str:
    from corda_tpu.core.contracts.exceptions import (
        TransactionResolutionException, TransactionVerificationException)
    from corda_tpu.core.crypto.signatures import SignatureException
    from corda_tpu.core.transactions.signed import \
        SignaturesMissingException
    if error is None:
        return trader_books.VALID
    if isinstance(error, SignaturesMissingException):
        return trader_books.MISSING
    if isinstance(error, SignatureException):
        return trader_books.BAD_SIGNATURE
    if isinstance(error, TransactionResolutionException):
        return trader_books.RESOLUTION
    if isinstance(error, TransactionVerificationException):
        return trader_books.CONTRACT
    return f"other: {error!r}"[:160]


def members_reached(level_sizes, verified: int) -> int:
    """The members of the levels a walk admits before it answers
    ``verified``: every level up to the one of its first failure, which it
    stops at (all of them where none failed). Each answer's sum is what the
    service's ``Verifier.WaveTx.*`` meters counted if every request was
    admitted, and answered, once."""
    upto = 0
    for n in level_sizes:
        upto += n
        if verified < upto:
            break
    return upto


def run(ctx) -> dict:
    if ctx.control not in CONTROLS:
        raise ValueError(f"driver bookwalk has no control {ctx.control!r}")
    the_program_runs_the_deployment(ctx)
    import corda_tpu.core.transactions  # noqa: F401  (the wire types)
    from corda_tpu.core.serialization import deserialize
    from corda_tpu.finance.cash import Cash
    from corda_tpu.finance.commercial_paper import CommercialPaper
    from corda_tpu.node.services import ResolvedFromWalk
    from corda_tpu.observability import (disable_tracing, enable_tracing,
                                         get_profiler, get_tracer)
    from corda_tpu.testing.services import MockServices
    from corda_tpu.utils.metrics import MetricRegistry
    from corda_tpu.verifier.batcher import SignatureBatcher
    from corda_tpu.verifier.service import make_verifier_service

    p = ctx.param
    ref = load_reference(ctx)
    n_books, n_trades = int(p("pool_books")), int(p("request_trades"))
    if (n_books, n_trades) != (int(p("books")), int(p("book_trades"))):
        raise bench_error(ctx, "a request is one book: the traffic's "
                          "pool_books x request_trades has to be the "
                          "configuration's books x book_trades")
    clients = int(p("clients"))
    altered_every, n_kinds = int(p("altered_every")), int(p("altered_kinds"))
    batcher_args = dict(p("batcher_args"))
    ladder = sorted(int(r) for r in batcher_args["bucket_ladder"])
    shipped = gc.get_threshold()
    collector = p("collector_thresholds")
    if ctx.trace:
        enable_tracing(int(p("trace_capacity", 262144)))

    # -- the books, made side by side -----------------------------------------
    t0 = time.perf_counter()
    jobs, turn = [], 0
    for k, s in enumerate(trader_books.book_seeds(ctx.seed, n_books)):
        kind = None
        if altered_every and k % altered_every == altered_every - 1:
            kind, turn = turn % n_kinds, turn + 1
        jobs.append((s, n_trades, int(p("banks")), kind))
    per_book = 5 * n_trades
    try:
        made = ecdsa_pool.parallel_map("trader_books:make_book", jobs,
                                       n_books * per_book)
    except ecdsa_pool.SignerUnavailable as e:
        raise bench_error(ctx, str(e))
    kinds = {k: m["kind"] for k, m in enumerate(made)
             if m["kind"] is not None}
    expected = [tuple(m["expect"]) for m in made]
    facts = [m["facts"] for m in made]
    books = []                  # (the levels, the walk's view of services)
    for m in made:
        levels = [[deserialize(b) for b in level] for level in m["levels"]]
        # the view computes every member's id, as a walk's fetch has
        books.append((levels, ResolvedFromWalk(
            MockServices(), [stx for level in levels for stx in level])))
    n_rows = sum(len(f["sigs"]) for book in facts for f in book)
    ctx.say("books", books=n_books, transactions=n_books * per_book,
            level_transactions=[len(lv) for lv in books[0][0]],
            level_rows=[sum(len(stx.sigs) for stx in lv)
                        for lv in books[0][0]],
            signatures=n_rows, signatures_per_tx=n_rows / (n_books * per_book),
            altered={k: trader_books.KINDS[kind]
                     for k, kind in kinds.items()},
            seconds=time.perf_counter() - t0)
    del made

    registry = MetricRegistry()
    if ctx.control == "unchecked_rows":
        batcher = UncheckedBatcher(registry, batcher_args["max_batch"])
    else:
        batcher = SignatureBatcher(metrics=registry, **batcher_args)
    service = make_verifier_service("Tpu", metrics=registry, batcher=batcher)
    the_rules = Cash.verify, CommercialPaper.verify
    if ctx.control == "rules_skipped":
        # CONTROL, never the program: both contracts accept everything
        Cash.verify = CommercialPaper.verify = lambda self, tx: None
    gc_watch = GcWatch()
    stop = threading.Event()
    lock = threading.Lock()
    done: list = []     # (book, t_submit, t_answer, verified, class)
    errors: list = []

    def client(c: int) -> None:
        k = c * (n_books // max(1, clients))
        try:
            while not stop.is_set():
                b = k % n_books
                levels, services = books[b]
                t_sub = time.perf_counter()
                with ctx.span("client.book"):
                    verified, error = service.verify_levels(
                        levels, services).result(timeout=BOOK_TIMEOUT_S)
                t_ans = time.perf_counter()
                with lock:
                    done.append((b, t_sub, t_ans, int(verified),
                                 class_of(error)))
                k += 1
        except Exception as e:      # surfaces as a failed run, not a hang
            errors.append(repr(e))
            stop.set()

    threads = []
    try:
        # -- every shape a device flush can take ------------------------------
        t0 = time.perf_counter()
        first_calls = {}
        if ctx.control != "unchecked_rows":
            rows = list(itertools.islice(
                ((sig.by, sig.bytes, stx.id.bytes)
                 for levels, _services in books for level in levels
                 for stx in level for sig in stx.sigs), ladder[-1]))
            if len(rows) < ladder[-1]:
                raise bench_error(
                    ctx, f"the pool holds {len(rows)} signature rows, under "
                    f"the ladder's top rung {ladder[-1]}")
            for rung in reversed(ladder):
                t1 = time.perf_counter()
                batcher.submit_group(rows[:rung]).result(
                    timeout=FIRST_CALL_TIMEOUT_S)
                first_calls[f"secp256k1@{rung}"] = time.perf_counter() - t1
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
        warm_answers = int(p("warm_answers"))
        while len(done) < warm_answers and not stop.is_set():
            time.sleep(0.02)
        if errors:
            raise RuntimeError(f"warm-up failed: {errors[0]}")
        ctx.say("loop", warm_answers=len(done),
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
        collector_cost = gc_watch.stop()
        snap1 = registry.snapshot()
        stop.set()
        for t in threads:
            t.join(timeout=BOOK_TIMEOUT_S)
        unjoined = sum(t.is_alive() for t in threads)
        ctx.trace_closes()
        snap_end = registry.snapshot()
        spans = []
        if ctx.trace:
            for trace_spans in get_tracer().traces().values():
                spans.extend(trace_spans)

        window_s = t_close - t_open
        inside = [row for row in done if t_open <= row[2] <= t_close]
        verified_inside = sum(row[3] for row in inside)
        book_s = sorted(row[2] - row[1] for row in inside)
        slices = [0] * 6
        for row in inside:
            slices[min(5, int((row[2] - t_open) / (window_s / 6)))] += row[3]
        ctx.say("window", tx_per_s=verified_inside / window_s,
                verified_inside=verified_inside, answers_inside=len(inside),
                answers_after=sum(row[2] > t_close for row in done),
                window_s=window_s,
                slice_rates=[n / (window_s / 6) for n in slices],
                book_ms_p50=book_s[len(book_s) // 2] * 1e3 if book_s else None,
                book_ms_max=book_s[-1] * 1e3 if book_s else None,
                **collector_cost)
        if ctx.trace:
            ctx.say("spans", recorded=len(spans),
                    capacity=int(p("trace_capacity", 262144)),
                    walks=sum(s.get("name") == "verifier.levels"
                              for s in spans))
        ctx.say("queue_depths_each_second", rows=depths)

        # -- every answer against the plain reference -------------------------
        t_ref = time.perf_counter()
        chunk = -(-n_books // ecdsa_pool.MAX_WORKERS)
        want = [tuple(w) for part in ecdsa_pool.parallel_map(
            f"{ref.__name__}:judge_all",
            [facts[i:i + chunk] for i in range(0, n_books, chunk)],
            n_books * per_book) for w in part]
        known = sum(w != e for w, e in zip(want, expected))
        differing, judged = [], {}
        for b, _s, _a, verified, found in done:
            if (verified, found) != want[b]:
                differing.append((b, (verified, found), want[b]))
            elif b in kinds:
                judged[kinds[b]] = judged.get(kinds[b], 0) + 1
        ctx.say("reference", books=len(want), answers_compared=len(done),
                altered_judged_by_kind=judged, first_differing=differing[:3],
                seconds=time.perf_counter() - t_ref)
        admitted = meter_delta(snap_loop, snap_end, "Verifier.WaveTx.bulk") \
            + meter_delta(snap_loop, snap_end, "Verifier.WaveTx.held")
        reached = sum(members_reached([len(lv) for lv in books[b][0]],
                                      verified)
                      for b, _s, _a, verified, _f in done)
        ctx.check("client_errors", len(errors) + unjoined, 0)
        ctx.check("answers_inside_window_missing", int(not inside), 0)
        ctx.check("reference_disagrees_with_altered_set", known, 0)
        ctx.check("answers_differing_from_reference", len(differing), 0)
        ctx.check("altered_kinds_never_judged_for_their_own_reason",
                  len(set(kinds.values()) - set(judged)), 0)
        ctx.check("altered_kinds_missing_from_the_pool",
                  n_kinds - len(set(kinds.values())), 0)
        ctx.check("requests_not_answered_exactly_once",
                  abs(admitted - reached), 0)
        ctx.check("members_of_levels_not_admitted_in_bulk",
                  meter_delta(snap_loop, snap_end, "Verifier.WaveTx.held"), 0)
        ctx.check("verifications_left_in_flight", abs(snap_end.get(
            "Verification.InFlight", {}).get("value", 0)), 0)
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
            ctx.check("rows_prepared_by_the_item_form_fallback",
                      meter_delta({}, snap_end, "SigBatcher.EcdsaItemsPrep"),
                      0)
            ctx.say("batcher", **b, device_rows_in_window=dev,
                    host_rows_in_window=hst,
                    flushes_by_padded_rows={
                        rows: n - warmed.get(rows, 0)
                        for rows, n in padded.items()},
                    flushes_by_reason={
                        n.rsplit(".", 1)[1]: meter_delta(snap0, snap1, n)
                        for n in snap1
                        if n.startswith("SigBatcher.DeviceFlush.")})
        return {"attempted": len(done),
                "failed": len(errors) + unjoined,
                "end_to_end": {"tx_per_s": verified_inside / window_s},
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
        Cash.verify, CommercialPaper.verify = the_rules
        for t in threads:
            t.join(timeout=30)
        service.shutdown()
        if ctx.trace:
            disable_tracing()
