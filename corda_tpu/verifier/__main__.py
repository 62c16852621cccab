"""Standalone verifier worker process — ``python -m corda_tpu.verifier``.

Reference parity: Verifier.main (verifier/src/main/.../Verifier.kt:42-79) —
a leaf process that attaches to a node's verification queue, consumes
requests, verifies, replies. Stateless: run N copies against one queue;
killing one redistributes its outstanding work (the node's redelivery
timeout or Goodbye handling, VerifierTests.kt:73+).

TPU-first: the worker runs the signature EC math through its own
``SignatureBatcher`` device kernels — consecutive requests' signatures
coalesce into one device batch, so N worker processes = N chips of
cross-transaction batched verification behind one competing-consumer queue.

Prints ``VERIFIER READY <host>:<port>`` on stdout once attached (the driver
DSL's readiness handshake, like the node's NODE READY line). On SIGTERM it
writes batcher metrics to ``--stats-file`` (if given) so tests can assert
device-verified work happened in this process, then exits cleanly.
"""
from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import signal
import sys
import threading


def _literal_resolve(name: str):
    """Workers address peers only as literal "host:port" strings."""
    host, _, port = name.rpartition(":")
    try:
        return host, int(port)
    except ValueError:
        return None


#: TPU_CHIPS_PER_PROCESS_BOUNDS for a process that owns this many chips.
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}


def _local_chip_count() -> int:
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


def confine_to_shard(shard_index: int, num_shards: int) -> tuple:
    """One process per chip: a TPU runtime that opens every local chip
    leaves nothing for the next worker, so a fleet worker tells the runtime
    — through its own visible-chips settings, BEFORE the backend first
    initialises — to open only this shard's chips. Returns the chip
    indices it confined itself to; () when there is nothing to confine (no
    local TPU, the operator already set the visibility, or a shard size
    the runtime has no bounds for) and the shard is cut from
    ``jax.devices()`` as before."""
    if os.environ.get("TPU_VISIBLE_CHIPS") or \
            os.environ.get("TPU_VISIBLE_DEVICES"):
        return ()
    chips = _local_chip_count()
    if not 0 <= shard_index < num_shards <= chips:
        return ()
    # the same contiguous split as parallel.shard_devices
    base, extra = divmod(chips, num_shards)
    start = shard_index * base + min(shard_index, extra)
    mine = tuple(range(start, start + base + (1 if shard_index < extra
                                              else 0)))
    if len(mine) not in _CHIP_BOUNDS:
        return ()
    os.environ["TPU_VISIBLE_CHIPS"] = ",".join(map(str, mine))
    os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] = _CHIP_BOUNDS[len(mine)]
    os.environ["TPU_PROCESS_BOUNDS"] = "1,1,1"
    return mine


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="corda-tpu-verifier")
    parser.add_argument("--queue-address", required=True,
                        help="host:port of the node whose queue to consume")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--no-device", action="store_true",
                        help="host-only verification (no kernels)")
    parser.add_argument("--host-crossover", type=int, default=None,
                        help="batches below this run on host (default: "
                             "the batcher's measured crossover)")
    parser.add_argument("--mesh-devices", type=int, default=None,
                        help="shard device batches over the first N local "
                             "chips (jax.sharding.Mesh; Verifier.kt's "
                             "scale-out seam, SPMD instead of N processes)")
    parser.add_argument("--num-shards", type=int, default=None,
                        help="fleet mode: split the visible devices into N "
                             "contiguous shards; this worker takes shard "
                             "--shard-index (run N workers, one per shard)")
    parser.add_argument("--shard-index", type=int, default=0,
                        help="which device shard this worker owns "
                             "(with --num-shards)")
    parser.add_argument("--capacity", type=int, default=None,
                        help="advertised relative capacity (default: the "
                             "shard's device count; the node router "
                             "normalizes load estimates by it)")
    parser.add_argument("--load-report-interval", type=float, default=0.5,
                        help="seconds between WorkerLoadReports to the node "
                             "router (0 disables)")
    parser.add_argument("--stats-file",
                        help="write batcher metrics JSON here on shutdown")
    parser.add_argument("--cordapp", action="append", default=None,
                        help="modules to import so contract/state types "
                             "deserialize (default: corda_tpu.finance + "
                             "corda_tpu.testing.dummy)")
    args = parser.parse_args(argv)

    confined: tuple = ()
    if args.num_shards is not None and not args.no_device:
        confined = confine_to_shard(args.shard_index, args.num_shards)

    for module in (args.cordapp if args.cordapp is not None
                   else ["corda_tpu.finance", "corda_tpu.testing.dummy"]):
        importlib.import_module(module)

    if not args.no_device:
        # repeated worker launches must not re-pay the kernel compiles
        from ..utils.compile_cache import enable_compile_cache
        enable_compile_cache()

    from ..network.tcp import TcpMessagingService
    from .batcher import SignatureBatcher
    from .out_of_process import VerifierWorker

    messaging = TcpMessagingService("verifier-worker", args.host, args.port,
                                    _literal_resolve)
    # the worker's reachable address IS its identity: the node replies and
    # deals work to exactly this host:port (no network-map registration,
    # same as the reference worker attaching straight to the broker)
    messaging._name = f"{args.host}:{messaging.port}"

    batcher_kwargs = {"use_device": not args.no_device}
    if args.host_crossover is not None:
        batcher_kwargs["host_crossover"] = args.host_crossover
    device_shard: tuple = ()
    if args.mesh_devices is not None and args.num_shards is not None:
        parser.error("--mesh-devices and --num-shards are exclusive: a "
                     "fleet worker owns a device shard, not the whole mesh")
    if args.mesh_devices is not None:
        from ..parallel import make_mesh
        batcher_kwargs["mesh"] = make_mesh(args.mesh_devices)
    elif args.num_shards is not None and not args.no_device:
        # fleet mode: this worker owns one contiguous shard of the visible
        # devices — a private mesh when the shard has several chips, a
        # plain device pin (no shard_map overhead) when it has one
        if confined:
            # the runtime shows this process only its own chips
            import jax
            shard = jax.devices()
            device_shard = confined
        else:
            from ..parallel import shard_devices
            shard = shard_devices(args.num_shards)[args.shard_index]
            device_shard = tuple(d.id for d in shard)
        if len(shard) > 1:
            from ..parallel import make_mesh
            batcher_kwargs["mesh"] = make_mesh(devices=shard)
        else:
            batcher_kwargs["device"] = shard[0]
    batcher = SignatureBatcher(**batcher_kwargs)
    worker = VerifierWorker(
        messaging, args.queue_address, batcher=batcher,
        use_device=not args.no_device,
        hello_interval_s=3.0,
        device_shard=device_shard, capacity=args.capacity,
        load_report_interval_s=(args.load_report_interval
                                if args.load_report_interval > 0 else None))

    print(f"VERIFIER READY {args.host}:{messaging.port}", flush=True)

    done = threading.Event()

    def _shutdown(signum, frame):
        done.set()

    signal.signal(signal.SIGTERM, _shutdown)
    signal.signal(signal.SIGINT, _shutdown)
    done.wait()

    if args.stats_file:
        snap = batcher.metrics.snapshot()
        with open(args.stats_file, "w") as f:
            json.dump({"verified_count": worker.verified_count,
                       "processed_sig_count": worker.processed_sig_count,
                       "device_shard": list(worker.device_shard),
                       "metrics": snap}, f)
    worker.stop()
    messaging.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
