"""Driver ``oopstream``: a node hands whole transactions to an out-of-process
verifier that owns the chip (``VerifierTests.kt``'s first case, at a size
that measures something).

Two processes, as deployed. The REQUESTOR is ``oop_requestor.py``, a child
with ``JAX_PLATFORMS=cpu``: an ``OutOfProcessTransactionVerifierService``
whose ``verify_signed`` resolves each transaction against its ledger and
sends it, serialised, over the TCP plane on loopback. The WORKER is a
``VerifierWorker`` in this process (which owns the chip, the profiler and
the tracer) with a ``SignatureBatcher`` built from ``batcher_args`` and
nothing else; it deserialises each request, runs the signatures through the
batcher, the contract rules on the host, and replies.

Set-up: the ledgers (``oop_ledgers.make_ledger``, in parallel processes);
the child started and loading them meanwhile; one ``submit_group`` of
exactly each rung of the configuration's ``bucket_ladder``, so that every
shape a device flush can take is compiled (``mark_warm()`` after); the
worker attached; the heap as it stands (the ledgers' facts, the modules)
collected once and frozen, and the collector set to the configuration's
``collector_thresholds`` in both processes (below); the closed loop started
and run until ``warm_responses`` answers are back, so that the pipeline is
full, every cache has seen the pool and the loop has settled when the window
opens: nothing pauses either process at the open. The window is the child's:
it notes its clock at the open and ``--seconds`` later, stops submitting and
waits for what is outstanding. ``tx_per_s`` is ``bench_common.window_rate``'s with one
response as the unit: responses that arrived inside the child's window over
its length; one that arrives after the close counts for nothing.

``correct`` (every limit 0 unless said): every answer of the run (warm-up,
window, drain) against the plain reference's verdict for that transaction;
the reference against the set of transactions made invalid; every request
answered exactly once and no response for an id never sent (the child
records every response frame's id); at most ``host_routed_limit`` (a share)
of the window's signature rows host-routed; no device flush of the window
at a padded row count that set-up did not run first
(``SigBatcher.DevicePadded.<rows>``); the worker's own meters equal to the
child's counts; and ``bench_common.check_device_path``.

The collector. Both processes hold thousands of decoded requests at any
moment, and a full collection walks them all: 56-59 of them a window in the
worker's process, 75-500 ms each with every thread stopped (``gc_s`` 3.6-8.3 s
of 30), and a pause of the requestor over 50 ms reads to the worker as a
pause of the stream. How long they take is what differed between runs (PERF.md
section 6, PR 37). ``collector_thresholds`` (the configuration's ``assumed``
has the reason) is handed to ``gc.set_threshold`` in both processes from the
loop's start to the run's end: the young generations as Python ships them,
the third so high that no full collection falls inside the window. The
window's line prints the collector's time in both processes.

A program whose batcher does not meter the padded row counts of its device
flushes (any parent of PR 37) gives the check of the ladder nothing to read:
the run ends with ``BenchError`` (exit 2) after set-up's first calls.

Controls (``--control``), each of which has to come out ``correct: false``:
``unchecked_rows`` gives the worker a stand-in batcher that calls every
signature valid, so the worker answers without verifying.
"""
from __future__ import annotations

import gc
import json
import os
import pathlib
import pickle
import queue
import subprocess
import sys
import threading
import time

import ecdsa_pool
import oop_ledgers
from bench_common import GcWatch, check_device_path, window_rate
from drivers.sigwaves import SCHEMES, bench_error, load_reference

BENCH = pathlib.Path(__file__).resolve().parent.parent
PADDED = "SigBatcher.DevicePadded."
HOST = "127.0.0.1"
#: a shape's first call (a cold compile is minutes), and the child's drain
FIRST_CALL_TIMEOUT_S = 1500.0
DRAIN_TIMEOUT_S = 180.0


class UncheckedBatcher:
    """CONTROL, never the program: the surface the worker uses of a
    batcher, every signature waved through."""

    max_latency_s = 0.005       # the program's default linger

    def __init__(self, metrics, max_batch):
        self.metrics = metrics
        self.max_batch = max_batch

    def submit_groups(self, groups, ctxs=None):
        from concurrent.futures import Future
        futures = []
        for checks in groups:
            fut: Future = Future()
            fut.set_result([True] * len(checks))
            futures.append(fut)
        return futures

    def breaker_status(self):
        return {}

    def queue_depths(self):
        return {}

    def close(self):
        pass


class Requestor:
    """The child process and its JSON lines. A thread reads them, so that a
    child that falls silent ends the run with an error and not a hang."""

    def __init__(self, ledgers_file, out_file):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.out_file = out_file
        self.proc = subprocess.Popen(
            [sys.executable, str(BENCH / "oop_requestor.py"),
             "--ledgers", str(ledgers_file), "--out", str(out_file)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=env)
        self.lines: queue.SimpleQueue = queue.SimpleQueue()
        threading.Thread(target=self._pump, daemon=True,
                         name="bench-requestor-lines").start()

    def _pump(self) -> None:
        for line in self.proc.stdout:
            self.lines.put(line)
        self.lines.put(None)

    def read(self, key: str, timeout_s: float = 300.0) -> dict:
        """The next line that carries ``key``."""
        deadline = time.monotonic() + timeout_s
        while True:
            try:
                line = self.lines.get(
                    timeout=max(0.0, deadline - time.monotonic()))
            except queue.Empty:
                raise RuntimeError(f"the requestor did not say {key!r} "
                                   f"within {timeout_s:.0f} s")
            if line is None:
                raise RuntimeError(
                    f"the requestor ended (exit {self.proc.wait()}) "
                    f"before saying {key!r}")
            try:
                row = json.loads(line)
            except ValueError:
                continue
            if key in row:
                return row

    def tell(self, **cmd) -> None:
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()

    def ask(self, key: str, timeout_s: float = 300.0, **cmd) -> dict:
        self.tell(**cmd)
        return self.read(key, timeout_s)

    def result(self) -> dict:
        with open(self.out_file, "rb") as f:
            return pickle.load(f)

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.tell(cmd="exit")
                self.proc.wait(timeout=10)
            except Exception:
                self.proc.kill()
                self.proc.wait(timeout=10)


def verdict_of(error) -> str:
    """An answer's error text -> the reference's vocabulary."""
    if error is None:
        return oop_ledgers.VALID
    if error.startswith("SignaturesMissingException"):
        return oop_ledgers.MISSING_SIGNER
    if "did not verify" in error:
        return oop_ledgers.BAD_SIGNATURE
    return f"other: {error[:120]}"


def where_the_requests_are(worker, messaging, batcher) -> list:
    """One look at the worker's side of the loop, for the run's notes:
    frames its transport holds, requests parked, requests in flight in the
    batcher, rows the batcher has queued."""
    queued = getattr(batcher, "queue_depths", dict)()
    return [getattr(messaging, "inbound_backlog", lambda: None)(),
            len(worker._backlog), worker._inflight_groups,
            sum(queued.values()),
            sum(getattr(batcher, "_inflight_n", {}).values()),
            sum(getattr(messaging, "_out_pending", {}).values())]


def padded_counts(snap: dict) -> dict:
    return {name[len(PADDED):]: row.get("count", 0)
            for name, row in snap.items() if name.startswith(PADDED)}


def meter_delta(snap0, snap1, name) -> int:
    return snap1.get(name, {}).get("count", 0) \
        - snap0.get(name, {}).get("count", 0)


def run(ctx) -> dict:
    import corda_tpu.core.transactions  # noqa: F401  (the wire types,
    import corda_tpu.testing.dummy  # noqa: F401      and the ledger's)
    from corda_tpu.core.crypto import schemes
    from corda_tpu.core.crypto.keys import PublicKey
    from corda_tpu.network.tcp import TcpMessagingService
    from corda_tpu.observability import (disable_tracing, enable_tracing,
                                         get_profiler, get_tracer)
    from corda_tpu.utils.metrics import MetricRegistry
    from corda_tpu.verifier.__main__ import _literal_resolve
    from corda_tpu.verifier.batcher import SignatureBatcher
    from corda_tpu.verifier.out_of_process import VerifierWorker

    p = ctx.param
    if ctx.control not in (None, "unchecked_rows"):
        raise ValueError(f"driver oopstream has no control {ctx.control!r}")
    (curve,) = p("schemes")
    scheme = getattr(schemes, SCHEMES[curve])
    ref = load_reference(ctx)
    n_ledgers, per_ledger = int(p("ledgers")), int(p("ledger_transactions"))
    invalid_every = int(p("invalid_every"))
    outstanding = int(p("outstanding"))
    batcher_args = dict(p("batcher_args"))
    ladder = [int(r) for r in batcher_args["bucket_ladder"]]
    shipped = gc.get_threshold()
    collector = [int(t) for t in p("collector_thresholds", shipped)]
    if ctx.trace:
        enable_tracing(int(p("trace_capacity", 262144)))

    # -- the ledgers, made side by side ---------------------------------------
    t0 = time.perf_counter()
    per_invalid = per_ledger // invalid_every if invalid_every else 0
    jobs = [(s, per_ledger, int(p("party_keys")), invalid_every,
             k * per_invalid)
            for k, s in enumerate(oop_ledgers.ledger_seeds(ctx.seed,
                                                           n_ledgers))]
    made = ecdsa_pool.parallel_map("oop_ledgers:make_ledger", jobs,
                                   n_ledgers * per_ledger)
    facts = [f for m in made for f in m["facts"]]
    kinds = {k * per_ledger + i: kind
             for k, m in enumerate(made) for i, kind in m["kinds"].items()}
    n_rows = sum(len(f[1]) for f in facts)
    ctx.state_dir.mkdir(parents=True, exist_ok=True)
    ledgers_file = ctx.state_dir / "ledgers.pickle"
    with open(ledgers_file, "wb") as f:
        pickle.dump([m["stx"] for m in made], f)
    del made
    ctx.say("ledgers", ledgers=n_ledgers, transactions=len(facts),
            signatures=n_rows, signatures_per_tx=n_rows / len(facts),
            invalid=len(kinds), seconds=time.perf_counter() - t0)

    requestor = Requestor(ledgers_file, ctx.state_dir / "answers.pickle")
    registry = MetricRegistry()
    messaging = worker = None
    gc_watch = GcWatch()
    if ctx.control == "unchecked_rows":
        batcher = UncheckedBatcher(registry, batcher_args["max_batch"])
    else:
        batcher = SignatureBatcher(metrics=registry, **batcher_args)
    try:
        queue_address = requestor.read("ready")["ready"]

        # -- every shape a device flush can take, before the worker attaches
        t0 = time.perf_counter()
        first_calls = {}
        if ctx.control is None:
            key_of: dict = {}
            rows = [(key_of.setdefault(pub, PublicKey(scheme, pub)), sig,
                     ref.transaction_id(blobs))
                    for blobs, sigs, _req in facts[:max(ladder)]
                    for pub, sig in sigs]
            for rung in sorted(ladder, reverse=True):
                t1 = time.perf_counter()
                batcher.submit_group(rows[:rung]).result(
                    timeout=FIRST_CALL_TIMEOUT_S)
                first_calls[str(rung)] = time.perf_counter() - t1
            del rows
        get_profiler().mark_warm()
        warmed = padded_counts(registry.snapshot())
        ctx.say("warm", rungs=ladder, first_call_s=first_calls,
                padded_rows_run=sorted(warmed, key=int),
                seconds=time.perf_counter() - t0)
        if ctx.control is None and sorted(warmed, key=int) \
                != [str(r) for r in sorted(ladder)]:
            raise bench_error(
                ctx, f"set-up dispatched the ladder {sorted(ladder)} and the "
                f"batcher's {PADDED}<rows> meters name the padded row counts "
                f"{sorted(warmed, key=int)}: the window's flushes cannot be "
                f"held to the shapes set-up ran")

        loaded = requestor.read("loaded")
        ctx.say("requestor", address=queue_address, **loaded)
        messaging = TcpMessagingService("verifier-worker", HOST, 0,
                                        _literal_resolve)
        messaging._name = f"{HOST}:{messaging.port}"
        worker = VerifierWorker(messaging, queue_address, batcher=batcher)

        # -- the closed loop, unmeasured until the pipeline is full ----------
        t0 = time.perf_counter()
        gc.collect()
        gc.freeze()
        gc.set_threshold(*collector)
        requestor.ask("started", cmd="start", outstanding=outstanding,
                      collector=collector)
        got = requestor.ask("responses", cmd="wait",
                            responses=int(p("warm_responses")))
        if got["errors"]:
            raise RuntimeError(f"warm-up failed: {got['errors'][0]}")
        ctx.say("loop", warm_responses=got["responses"],
                seconds=time.perf_counter() - t0)
        gc_watch.start()
        snap0 = registry.snapshot()

        ctx.window_opens()
        wall_open = time.time()
        requestor.tell(cmd="window", seconds=ctx.seconds)
        states = []
        for _tick in range(max(1, int(ctx.seconds))):
            time.sleep(ctx.seconds / max(1, int(ctx.seconds)))
            states.append(where_the_requests_are(worker, messaging, batcher))
        wall_close = time.time()
        snap1 = registry.snapshot()
        collector = gc_watch.stop()
        done = requestor.read("done", DRAIN_TIMEOUT_S)
        ctx.trace_closes()
        out = requestor.result()
        snap_end = registry.snapshot()
        spans = []
        if ctx.trace:
            for trace_spans in get_tracer().traces().values():
                spans.extend(trace_spans)

        window_s = out["t_close"] - out["t_open"]
        rate = window_rate([t - out["t_open"] for _i, _e, t in out["answers"]
                            if t >= out["t_open"]], window_s, 1)
        rate["tx_per_s"] = rate.pop("sigs_per_s")
        rate["responses_inside"] = rate.pop("waves_completed_inside")
        rate["responses_after"] = rate.pop("waves_finished_after")
        ctx.say("window", **rate, **collector, **done["done"],
                open_skew_s=out["t_open"] - wall_open)
        if ctx.trace:
            ctx.say("spans", recorded=len(spans),
                    capacity=int(p("trace_capacity", 262144)),
                    decode=sum(s.get("name") == "worker.decode"
                               for s in spans))
        ctx.say("worker_each_second",
                columns=["transport", "parked", "in_flight", "batcher_rows",
                         "batcher_flushes", "replies_unsent"],
                rows=states)

        # -- every answer against the plain reference ------------------------
        t_ref = time.perf_counter()
        chunk = -(-len(facts) // max(1, n_ledgers))
        want = [v for part in ecdsa_pool.parallel_map(
            f"{ref.__name__}:verdicts",
            [facts[i:i + chunk] for i in range(0, len(facts), chunk)],
            len(facts)) for v in part]
        known = sum(v != (oop_ledgers.VERDICTS[kinds[i]] if i in kinds
                          else oop_ledgers.VALID)
                    for i, v in enumerate(want))
        differing = [(i, e) for i, e, _t in out["answers"]
                     if verdict_of(e) != want[i]]
        refused = {}
        for i, e, _t in out["answers"]:
            if i in kinds and verdict_of(e) == want[i]:
                refused[kinds[i]] = refused.get(kinds[i], 0) + 1
        ctx.say("reference", transactions=len(want),
                answers_compared=len(out["answers"]),
                invalid_refused_by_kind=refused,
                first_differing=differing[:3],
                seconds=time.perf_counter() - t_ref)
        ids = out["response_ids"]
        sent = set(range(1, out["dispatched"] + 1))
        exactly_once = {"duplicates": len(ids) - len(set(ids)),
                        "for_an_id_never_sent": len(set(ids) - sent),
                        "unanswered": len(sent - set(ids))
                        + out["unanswered"]}
        ctx.check("requestor_errors", len(out["errors"]), 0)
        ctx.check("responses_inside_window_missing",
                  int(rate["responses_inside"] == 0), 0)
        ctx.check("reference_disagrees_with_invalid_set", known, 0)
        ctx.check("answers_differing_from_reference", len(differing), 0)
        ctx.check("invalid_kinds_never_refused",
                  len(set(kinds.values()) - set(refused)), 0)
        ctx.check("requests_not_answered_exactly_once",
                  sum(exactly_once.values()), 0)
        ctx.say("exactly_once", responses=len(ids), **exactly_once)
        ctx.check("worker_requests_in_beside_the_requestors",
                  abs(meter_delta({}, snap_end, "Verifier.RequestsIn")
                      - out["dispatched"]), 0)
        ctx.check("worker_responses_out_beside_the_requestors",
                  abs(meter_delta({}, snap_end, "Verifier.ResponsesOut")
                      - len(ids)), 0)
        if ctx.control is None:
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
            ctx.say("batcher", **b, device_rows_in_window=dev,
                    host_rows_in_window=hst,
                    flushes_by_padded_rows=padded,
                    flushes_by_reason={
                        n.rsplit(".", 1)[1]: meter_delta(snap0, snap1, n)
                        for n in snap1
                        if n.startswith("SigBatcher.DeviceFlush.")})
        return {"attempted": len(out["answers"]),
                "failed": len(out["errors"]) + out["unanswered"],
                "end_to_end": {"tx_per_s": rate["tx_per_s"]},
                "layer_data": {"snap0": snap0, "snap1": snap1,
                               "spans": spans,
                               "window_wall": (wall_open, wall_close),
                               # when the traced sub-window opened: the
                               # reader of the kernel's shapes asks the
                               # dispatch spans inside it
                               "trace_wall_t0": ctx.trace_segments[0][1]
                               if ctx.trace_segments else None,
                               "gap_prefixes": ("batcher.", "worker.")}}
    finally:
        gc_watch.stop()
        gc.set_threshold(*shipped)
        gc.unfreeze()
        requestor.close()
        if worker is not None:
            worker.stop(announce=False)
        elif batcher is not None:
            batcher.close()
        if messaging is not None:
            messaging.stop()
        if ctx.trace:
            disable_tracing()
