#!/usr/bin/env python3
"""The node's half of the ``genledger-oop`` deployment, as a process of its
own: an ``OutOfProcessTransactionVerifierService`` (with its
``VerifierRequestQueue``) on a TCP endpoint, holding the ledgers in its
services, which keeps a fixed number of ``verify_signed`` requests
outstanding against whatever verifier worker attaches.

    python3 benchmarks/oop_requestor.py --ledgers FILE --out FILE

The driver (``drivers/oopstream.py``) starts it with ``JAX_PLATFORMS=cpu``;
it imports nothing that opens a device. It speaks JSON lines: on stdout
``{"ready": "host:port"}`` once the endpoint listens, ``{"loaded": n}`` once
the ledgers are resolvable, then one answer per command read from stdin:

    {"cmd": "start", "outstanding": K,   begin the closed loop -> {"started"};
     "collector": [g0, g1, g2]}          ``gc.set_threshold`` first, if given
    {"cmd": "wait", "responses": N}      -> {"responses": n} once n >= N
    {"cmd": "window", "seconds": S}      open the window now, close it S
                                         seconds later on this process's
                                         clock, stop submitting, wait for
                                         what is outstanding, write --out
                                         -> {"done": {...}}
    {"cmd": "exit"}

The loop: transaction ``i`` of the pool (the ledgers one after another, each
in ledger order, round and round) goes to ``verify_signed`` with its own
ledger's services; whenever an answer arrives the next transaction is sent
from the thread that delivered it, so ``outstanding`` stay in flight. A
transaction refused before dispatch (signatures missing) is an answer too.
``--out`` gets a pickle: every answer as ``(pool index, error text or None,
arrival on time.time())``, the window's two ends, the verification ids of
every response frame that arrived (for exactly-once), the counts.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import pickle
import sys
import threading
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
HOST = "127.0.0.1"
DRAIN_TIMEOUT_S = 120.0     # under the driver's own wait for "done"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def literal_resolve(name: str):
    host, _, port = name.rpartition(":")
    try:
        return host, int(port)
    except ValueError:
        return None


class ClosedLoop:
    """``outstanding`` requests in flight from ``start()`` to ``stop()``."""

    def __init__(self, service, pool):
        self.service = service
        self.pool = pool                    # [(stx, services)]
        self.lock = threading.Lock()
        self.outstanding = 0
        self.in_flight = 0
        self.cursor = 0
        self.dispatched = 0                 # went to the queue (got an id)
        self.refused_before_dispatch = 0
        self.stopped = False
        self.answers: list = []             # (pool index, error, t_arrival)
        self.errors: list = []
        self.idle = threading.Event()

    def start(self, outstanding: int) -> None:
        self.outstanding = outstanding
        self.pump()

    def stop(self) -> None:
        with self.lock:
            self.stopped = True
            if self.in_flight == 0:
                self.idle.set()

    def pump(self) -> None:
        while True:
            with self.lock:
                if self.stopped or self.in_flight >= self.outstanding:
                    return
                i = self.cursor
                self.cursor = (i + 1) % len(self.pool)
                self.in_flight += 1
            stx, services = self.pool[i]
            try:
                fut = self.service.verify_signed(stx, services)
            except Exception as e:      # surfaces as a failed run, not a hang
                self.errors.append(repr(e))
                self.stop()
                return
            # the thread that started the loop and the one that delivers
            # answers both pump for a moment: the counts are kept under the lock
            if fut.done():
                with self.lock:
                    self.refused_before_dispatch += 1
                self.answered(i, fut, again=False)
            else:
                with self.lock:
                    self.dispatched += 1
                fut.add_done_callback(lambda f, i=i: self.answered(i, f))

    def answered(self, i: int, fut, again: bool = True) -> None:
        t = time.time()
        exc = fut.exception()
        error = None if exc is None else f"{type(exc).__name__}: {exc}"
        with self.lock:
            self.answers.append((i, error, t))
            self.in_flight -= 1
            if self.stopped and self.in_flight == 0:
                self.idle.set()
        if again:
            self.pump()


def say(**row) -> None:
    print(json.dumps(row), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--ledgers", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import corda_tpu.core.transactions  # noqa: F401  (the wire types)
    import corda_tpu.testing.dummy  # noqa: F401  (the ledger's types)
    from bench_common import GcWatch
    from corda_tpu.core.serialization import deserialize
    from corda_tpu.network.messaging import (TOPIC_VERIFIER_RESPONSES,
                                             TopicSession)
    from corda_tpu.network.tcp import TcpMessagingService
    from corda_tpu.testing.services import MockServices
    from corda_tpu.verifier.out_of_process import \
        OutOfProcessTransactionVerifierService

    messaging = TcpMessagingService("requestor", HOST, 0, literal_resolve)
    messaging._name = f"{HOST}:{messaging.port}"
    service = OutOfProcessTransactionVerifierService(messaging)
    # every response frame's id, as it arrives: a duplicate or an id never
    # sent is dropped in silence by the service, and has to be seen here
    response_ids: list = []
    messaging.add_message_handler(
        TopicSession(TOPIC_VERIFIER_RESPONSES),
        lambda msg: response_ids.append(
            deserialize(msg.data).verification_id))
    say(ready=messaging.my_address)

    t0 = time.perf_counter()
    with open(args.ledgers, "rb") as f:
        ledgers = pickle.load(f)
    pool = []
    for blobs in ledgers:
        services = MockServices()
        txs = [deserialize(b) for b in blobs]
        services.record_transactions(*txs)      # resolves, and primes ids
        pool.extend((stx, services) for stx in txs)
    del ledgers
    gc.collect()
    gc.freeze()
    say(loaded=len(pool), seconds=time.perf_counter() - t0)

    loop = ClosedLoop(service, pool)
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd["cmd"] == "start":
            while service.queue.worker_count < 1:
                time.sleep(0.01)
            if cmd.get("collector"):
                gc.set_threshold(*cmd["collector"])
            loop.start(int(cmd["outstanding"]))
            say(started=True)
        elif cmd["cmd"] == "wait":
            while len(loop.answers) < int(cmd["responses"]) \
                    and not loop.stopped:
                time.sleep(0.02)
            say(responses=len(loop.answers), errors=loop.errors)
        elif cmd["cmd"] == "window":
            gc_watch = GcWatch().start()
            t_open = time.time()
            time.sleep(float(cmd["seconds"]))
            t_close = time.time()
            collector = gc_watch.stop()
            loop.stop()
            drained = loop.idle.wait(timeout=DRAIN_TIMEOUT_S)
            t_end = time.time()
            time.sleep(0.2)         # a late duplicate would arrive now
            snap = service.metrics.snapshot()
            out = {"answers": loop.answers, "t_open": t_open,
                   "t_close": t_close, "response_ids": response_ids,
                   "dispatched": loop.dispatched,
                   "refused_before_dispatch": loop.refused_before_dispatch,
                   "unanswered": loop.in_flight, "drained": drained,
                   "drain_s": t_end - t_close, "errors": loop.errors,
                   "requestor_collector": collector,
                   "verification": {
                       k: snap[k].get("count") for k in
                       ("Verification.Success", "Verification.Failure")
                       if k in snap}}
            with open(args.out, "wb") as f:
                pickle.dump(out, f)
            say(done={k: out[k] for k in (
                "dispatched", "refused_before_dispatch", "unanswered",
                "drained", "drain_s", "errors", "requestor_collector")})
        elif cmd["cmd"] == "exit":
            break
    service.shutdown()
    messaging.stop()
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
