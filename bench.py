"""Verifier benchmark: signature verifies/sec/chip, all device schemes.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} plus
per-scheme keys.  The primary metric/value is ECDSA-secp256k1; the same
artifact carries the Ed25519 (the reference's DEFAULT scheme,
Crypto.kt:119,170) and secp256r1 kernel rates, the per-scheme and
mixed-scheme service rates, and the p50 latencies.

No on-chip number exists for today's code (ROADMAP.md A1 replaces this
runner). Modes without --smoke refuse to start unless JAX reports a TPU;
``python chip_smoke.py`` is the proof that the path runs on the chip.

vs_baseline is measured against single-threaded host-CPU verification via
the `cryptography` (OpenSSL) package — the stand-in for the reference's
single-threaded JVM `Crypto.doVerify` replay (BASELINE.json config 1;
OpenSSL is strictly faster than the JVM/BouncyCastle path, so this
under-reports our advantage rather than inflating it).

Env knobs:
  CORDA_TPU_BENCH_N       batch size (default 32768; use 256 to smoke-test)
  CORDA_TPU_BENCH_UNIQUE  1 → sign a fully-unique batch (no tiling) for the
                          gather-locality A/B (VERDICT r4 weak #6); slow
                          (pure-Python signing), meant for one-off runs.
                          Covers every scheme incl. secp256r1 (make_items
                          takes the curve), so the half-gcd split path's
                          per-item windows/tables get the same A/B.

Flags:
  --smoke    tiny-batch wiring check: exercises the FULL service path
             (SignatureBatcher drain → per-scheme prep pool → resolve)
             on the host-crossover route only — every batch stays under
             ``host_crossover`` so no device kernel compiles, making it
             fast enough for a tier-1 CPU test (tests/test_bench_smoke.py).
             Kernel-rate fields are emitted as 0.0 and "smoke": true is
             added; every other JSON field keeps its shape.
  --ledger   end-to-end ledger scenario (observability/ledger_harness.py):
             open-loop finance flows (issue → pay → DvP settle) against a
             raft notary with the verifier service on the commit path;
             emits the LEDGER_r0*.json fields (committed_tx_per_sec,
             per-stage p50/p90/p99, SLO budget, chaos windows). The full
             shape arms the chaos windows; with --smoke it is the tiny
             CPU tier-1 shape, chaos off. Exactly-once / agreement /
             stitched-trace violations exit 1 as BENCH INVALID.
  --guard    regression gate (corda_tpu.tools.benchguard): after printing
             the artifact, check it against floors fit from the repo's
             BENCH_r*.json trajectory (best-so-far minus a documented
             tolerance) and exit 1 with a readable diff on a breach. With
             --smoke the gate degrades to a schema check (zeroed kernel
             rates carry no information), so `--smoke --guard` is CI-safe.
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import time

import numpy as np

import jax

from corda_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()

from corda_tpu.core.crypto import ecmath
from corda_tpu.ops import ed25519 as ed_ops
from corda_tpu.ops import weierstrass as wc_ops

SMOKE = "--smoke" in sys.argv
GUARD = "--guard" in sys.argv
FLEET = "--fleet" in sys.argv
LEDGER = "--ledger" in sys.argv
SOAK = "--soak" in sys.argv
# smoke: small enough that every per-scheme drain stays below the batcher's
# host_crossover (192) even when REPS groups coalesce into one flush
BATCH = int(os.environ.get("CORDA_TPU_BENCH_N", 48 if SMOKE else 32768))
UNIQUE = (BATCH if os.environ.get("CORDA_TPU_BENCH_UNIQUE")
          else (16 if SMOKE else 512))
REPS = 1 if SMOKE else 3
SERVICE_RUNS = 1 if SMOKE else 3
                   # service numbers are medians of SERVICE_RUNS runs


def _tile(base, n):
    return (base * (n // len(base) + 1))[:n]


def make_items(n: int, curve=None):
    """ECDSA items [(priv, pub, msg, r, s)]; UNIQUE distinct, tiled to n."""
    curve = curve or ecmath.SECP256K1
    rng = np.random.default_rng(123)
    base = []
    for _ in range(min(n, UNIQUE)):
        priv = int.from_bytes(rng.bytes(32), "little") % (curve.n - 1) + 1
        pub = curve.mul(priv, curve.g)
        msg = rng.bytes(64)
        r, s = ecmath.ecdsa_sign(curve, priv, msg)
        base.append((priv, pub, msg, r, s))
    return _tile(base, n)


def make_ed_items(n: int):
    """Ed25519 items [(pub32, sig64, msg)]."""
    rng = np.random.default_rng(321)
    base = []
    for _ in range(min(n, UNIQUE)):
        seed = rng.bytes(32)
        pub = ecmath.ed25519_public_key(seed)
        msg = rng.bytes(64)
        base.append((pub, ecmath.ed25519_sign(seed, msg), msg))
    return _tile(base, n)


def host_baseline_rate(items) -> float:
    """Single-threaded OpenSSL ECDSA-secp256k1 verify rate (verifies/sec)."""
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec
    from cryptography.hazmat.primitives.asymmetric.utils import (
        encode_dss_signature)
    keys, sigs = [], []
    for priv, pub, msg, r, s in items:
        keys.append(ec.derive_private_key(priv, ec.SECP256K1()).public_key())
        sigs.append(encode_dss_signature(r, s))
    t0 = time.perf_counter()
    for (priv, pub, msg, r, s), key, der in zip(items, keys, sigs):
        key.verify(der, msg, ec.ECDSA(hashes.SHA256()))
    dt = time.perf_counter() - t0
    return len(items) / dt


def _kernel_rate(prep_args, fn) -> float:
    ok = np.asarray(fn(*prep_args))  # compile + warm
    assert bool(ok.all()), "benchmark signatures must all verify"
    t0 = time.perf_counter()
    for _ in range(REPS):
        # the host copy is a hard sync: the timing ends in a forced result
        ok = np.asarray(fn(*prep_args))
    dt = time.perf_counter() - t0
    return ok.shape[0] * REPS / dt


def device_rate(items) -> float:
    import functools
    kitems = [(pub, msg, r, s) for _, pub, msg, r, s in items]
    *args, pre = wc_ops.prepare_batch_hybrid_wide(
        kitems, wc_ops.HYBRID_G_WINDOW)
    assert np.asarray(pre).all()
    return _kernel_rate(args, functools.partial(
        wc_ops._verify_kernel_hybrid_wide, g_w=wc_ops.HYBRID_G_WINDOW))


#: Doublings per verify in the production r1 kernel: the half-gcd split
#: ladder runs 8 outer steps × 16 bits with step 0 peeled (128 − 4), vs
#: 252 for the retired full-width windowed ladder.
R1_DOUBLINGS_PER_OP = 124.0


def r1_device_rate(items) -> tuple[float, float]:
    """(verifies/s, halfgcd fallback %) for the r1 half-gcd split kernel.
    The benchmark corpus is honestly-signed, so the fallback rate should
    be 0.0 (r + n < p has ~2^-64 probability for honest r) — the field is
    emitted so a regression in the split prep shows up in the artifact."""
    import functools
    kitems = [(pub, msg, r, s) for _, pub, msg, r, s in items]
    wc_ops.r1_split_stats(reset=True)
    *args, pre, forced = wc_ops.prepare_batch_r1_split(
        ecmath.SECP256R1, kitems, wc_ops.R1_G_WINDOW)
    stats = wc_ops.r1_split_stats()
    fallback_pct = 100.0 * stats["fallback"] / max(1, stats["items"])
    assert np.asarray(pre).all() and not forced.any()
    rate = _kernel_rate(args, functools.partial(
        wc_ops._verify_kernel_r1_split, curve_name="secp256r1",
        w=wc_ops.R1_G_WINDOW))
    return rate, fallback_pct


def ed_device_rate(items) -> float:
    import functools
    *args, pre = ed_ops.prepare_batch_split(items, ed_ops.SPLIT_B_WINDOW)
    assert np.asarray(pre).all()
    return _kernel_rate(args, functools.partial(
        ed_ops._verify_kernel_split, w=ed_ops.SPLIT_B_WINDOW))


def _ecdsa_triples(items, curve, scheme):
    from corda_tpu.core.crypto.keys import PublicKey, sec1_compress
    return [(PublicKey(scheme, sec1_compress(curve, pub)),
             ecmath.ecdsa_sig_to_der(r, s), msg)
            for _, pub, msg, r, s in items]


def _k1_triples(items):
    from corda_tpu.core.crypto.schemes import ECDSA_SECP256K1_SHA256
    return _ecdsa_triples(items, ecmath.SECP256K1, ECDSA_SECP256K1_SHA256)


def _ed_triples(items):
    from corda_tpu.core.crypto.keys import PublicKey
    from corda_tpu.core.crypto.schemes import EDDSA_ED25519_SHA512
    return [(PublicKey(EDDSA_ED25519_SHA512, pub), sig, msg)
            for pub, sig, msg in items]


def _service_warm(batcher, triples) -> None:
    """Warm one stream at the SAME depth as the timed loop, plus every
    bucket-ladder rung the continuous planner can cut from it, so all
    shapes the timed loop will see compile HERE (a fresh EC bucket kernel
    costs minutes to compile for a v5e, persistent-cached afterwards).
    mark_warm() after all warms makes any later compile a counted
    regression (post_warmup_compiles)."""
    warm = [batcher.submit_group(triples) for _ in range(REPS)]
    for wf in warm:
        assert all(wf.result(timeout=3000))
    for rung in batcher._default_ladder:
        if rung >= len(triples):
            break
        assert all(batcher.submit_group(triples[:rung]).result(timeout=3000))


def _service_rate_for(batcher, triples) -> float:
    """Median continuous-stream rate over SERVICE_RUNS runs (all reps
    queued up front so batch N+1's host prep overlaps batch N's device
    round-trip — the service's steady-state shape). Streams must be warmed
    via _service_warm first."""
    rates = []
    for _ in range(SERVICE_RUNS):
        t0 = time.perf_counter()
        group_futures = [batcher.submit_group(triples) for _ in range(REPS)]
        for gf in group_futures:
            assert all(gf.result(timeout=600))
        rates.append(len(triples) * REPS / (time.perf_counter() - t0))
    return statistics.median(rates)


def _pctl(sorted_samples, q: float) -> float:
    """Nearest-rank percentile of an already-sorted sample list."""
    idx = min(len(sorted_samples) - 1, int(q * len(sorted_samples)))
    return sorted_samples[idx]


def service_metrics(k1_items, ed_items, r1_items) -> dict:
    """Service-path numbers through the SignatureBatcher seam (host prep +
    device kernel + future resolution — what a node actually gets): k1,
    ed25519, r1, and a mixed-scheme stream; p50 @ batch=1 and p50/p90/p99
    @ batch=1k (interactive class); the prep-overlap high-water mark; and
    the post-warmup compile count (zero when the bucket ladder kept the
    jit cache hot through the whole timed phase)."""
    from corda_tpu.core.crypto.schemes import ECDSA_SECP256R1_SHA256
    from corda_tpu.observability import get_profiler, stage_percentiles
    from corda_tpu.utils.metrics import MetricRegistry
    from corda_tpu.verifier.batcher import SignatureBatcher

    k1_triples = _k1_triples(k1_items)
    ed_triples = _ed_triples(ed_items)
    r1_full = _ecdsa_triples(r1_items, ecmath.SECP256R1,
                             ECDSA_SECP256R1_SHA256)
    n = len(k1_triples)
    # GeneratedLedger-style mix (BASELINE config 2 direction): the default
    # scheme dominates, k1 heavy, r1 present (VerifierTests.kt:37-100 uses
    # mixed generated ledgers as the verification corpus)
    mixed = (ed_triples[: int(0.45 * n)] + k1_triples[: int(0.45 * n)]
             + r1_full[: max(1, n - 2 * int(0.45 * n))])
    registry = MetricRegistry()
    # the kernel flight recorder's gauges/histograms ride the same snapshot
    prof = get_profiler()
    prof.publish(registry)
    batcher = SignatureBatcher(metrics=registry)
    sub = k1_triples[:1024]
    try:
        # warm EVERY stream (and the interactive 1k bucket + a single
        # submit) before the warmup boundary: after mark_warm() the timed
        # phase must run entirely on the hot jit cache — any compile past
        # this point counts in post_warmup_compiles
        for stream in (k1_triples, ed_triples, r1_full, mixed):
            _service_warm(batcher, stream)
        assert all(batcher.submit_group(
            sub, latency_class="interactive").result(timeout=900))
        key0, der0, msg0 = k1_triples[0]
        assert batcher.submit(key0, der0, msg0).result(timeout=900)
        prof.mark_warm()
        k1_rate = _service_rate_for(batcher, k1_triples)
        ed_rate = _service_rate_for(batcher, ed_triples)
        r1_rate = _service_rate_for(batcher, r1_full)
        mixed_rate = _service_rate_for(batcher, mixed)
        latencies = []
        for i in range(5 if SMOKE else 41):
            key, der, msg = k1_triples[i % len(k1_triples)]
            t0 = time.perf_counter()
            assert batcher.submit(key, der, msg).result(timeout=60)
            latencies.append(time.perf_counter() - t0)
        p50_ms = sorted(latencies)[len(latencies) // 2] * 1000.0
        # mid-size-batch latency (VERDICT r3 weak #5 / r4 #7): the band
        # between the host crossover (192) and dispatch-floor amortization
        # (~8k). Submitted as the INTERACTIVE class — the latency-bound
        # path a node's verify_signed actually rides — so these tails
        # measure the short-deadline flush, not the bulk linger.
        # (--smoke holds BATCH below the crossover, so `sub` stays on the
        # host route there — same submit shape, no kernel compile.)
        mid = []
        for _ in range(3 if SMOKE else 11):
            t0 = time.perf_counter()
            assert all(batcher.submit_group(
                sub, latency_class="interactive").result(timeout=120))
            mid.append(time.perf_counter() - t0)
        mid.sort()
        p50_1k_ms = mid[len(mid) // 2] * 1000.0
        p90_1k_ms = _pctl(mid, 0.90) * 1000.0
        p99_1k_ms = _pctl(mid, 0.99) * 1000.0
        # the numbers above are only device numbers if the device was
        # actually used: an open breaker means some batches silently took
        # the host path, which would corrupt the bench without failing it
        breakers = batcher.breaker_status()
        tripped = {s: st for s, st in breakers.items()
                   if st["state"] != "closed" or st["trips"]}
        if tripped:
            print(f"BENCH INVALID: device circuit breaker engaged during "
                  f"the run: {tripped}", file=sys.stderr)
            sys.exit(1)
        # one failed device batch is host-verified without tripping
        # anything: it too makes the numbers above not device numbers
        failures = registry.meter("SigBatcher.BatchFailure").count
        if failures:
            print(f"BENCH INVALID: {failures} device batch(es) failed and "
                  f"were verified on the host instead", file=sys.stderr)
            sys.exit(1)
    finally:
        batcher.close()
    # per-stage latency breakdown (prep / dispatch / finish percentiles)
    # from the batcher's histograms — where a verify's time actually went
    snap = registry.snapshot()
    stages = stage_percentiles(snap)
    overlap = snap.get("SigBatcher.PrepActive", {}).get("max", 0)
    return {
        "k1_rate": k1_rate, "ed_rate": ed_rate, "r1_rate": r1_rate,
        "mixed_rate": mixed_rate, "p50_ms": p50_ms, "p50_1k_ms": p50_1k_ms,
        "p90_1k_ms": p90_1k_ms, "p99_1k_ms": p99_1k_ms, "stages": stages,
        "overlap": overlap,
        "post_warmup_compiles": prof.compiles_since_warm(),
        "bucket_ladder": list(batcher._default_ladder),
        "interactive_latency_ms": batcher.interactive_latency_s * 1000.0,
        "interactive_batch": batcher.interactive_batch,
    }


def _fleet_http_probe() -> dict:
    """Smoke acceptance for the fleet observability plane, over REAL HTTP:
    serve a live 2-worker fleet through NodeWebServer and check that
    (a) /metrics carries at least one worker-labeled federated family,
    (b) /traces returns a stitched trace holding node-side AND worker-side
    spans for one request, and (c) /debug/requests has lifecycle timelines.
    Returns {"http_federated_families": int, "http_stitched_traces": int,
    "http_request_timelines": int}."""
    import urllib.request
    from corda_tpu.observability import Tracer, get_tracer, set_tracer
    from corda_tpu.tools.webserver import NodeWebServer
    from corda_tpu.verifier.fleet import InProcessFleet, make_sig_checks

    class FleetOps:
        """Minimal ops surface: just what the observability endpoints use."""
        def __init__(self, fleet):
            self._fleet = fleet

        def metrics_snapshot(self):
            return self._fleet.metrics.snapshot()

        def fleet_status(self):
            return self._fleet.service.fleet_status()

        def request_timelines(self, limit=None):
            return self._fleet.service.request_log.snapshot(limit=limit)

    prev_tracer = get_tracer()
    set_tracer(Tracer(capacity=4096))
    fleet = InProcessFleet(2, use_device=False)
    web = NodeWebServer(FleetOps(fleet)).start()
    try:
        checks = make_sig_checks(16)
        for f in [fleet.verify_signatures(checks) for _ in range(8)]:
            f.result(timeout=120)
        time.sleep(0.05)   # let the pump deliver the next load reports

        def fetch(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{web.port}{path}", timeout=10) as r:
                return r.read().decode()

        metrics_text = fetch("/metrics")
        federated = {line.split("{", 1)[0] for line in metrics_text.splitlines()
                     if 'worker="' in line and not line.startswith("#")}
        traces = json.loads(fetch("/traces")).get("traces", {})
        stitched = 0
        for spans in traces.values():
            names = [s.get("name", "") for s in spans]
            if ("verifier.oop_submit" in names
                    and any(n.startswith("worker.") for n in names)):
                stitched += 1
        timelines = json.loads(fetch("/debug/requests"))["requests"]
        return {"http_federated_families": len(federated),
                "http_stitched_traces": stitched,
                "http_request_timelines": len(timelines)}
    finally:
        web.stop()
        fleet.close()
        set_tracer(prev_tracer)


def fleet_main() -> None:
    """--fleet: the multi-worker topology bench (corda_tpu.verifier.fleet).
    Smoke: 2 in-process host-route workers, no kernel compiles — a tier-1
    wiring check that the router deals to BOTH workers, every future
    resolves, and (via a real HTTP probe) the observability plane
    federates worker metrics and stitches cross-process traces. Full: one
    device-pinned worker per local chip."""
    from corda_tpu.verifier.fleet import fleet_bench, kill_storm_recovery
    if SMOKE:
        out = fleet_bench(2, groups=24, group_size=16, use_device=False)
        out["smoke"] = True
        out.update(_fleet_http_probe())
    else:
        import jax
        devices = jax.devices()
        n = min(8, len(devices))
        out = fleet_bench(n, groups=32 * n, group_size=256,
                          use_device=True, devices=devices[:n],
                          host_crossover=0)
        # full runs also prove self-healing: a seeded kill-storm (host
        # path — the controller seams are device-agnostic) whose measured
        # recovery time becomes the artifact's recovery_s
        storm = kill_storm_recovery(seed=7)
        out["kill_storm"] = storm
        out["recovery_s"] = storm["recovery_s"] or 0.0
        out["controller_actions"] = storm["controller_actions"]
    out["fleet"] = True
    problems = []
    if SMOKE:
        # an unstressed run must leave the controller idle: state steady,
        # zero actions, nothing to recover from (benchguard schema-locked)
        if out.get("controller_state") != "steady":
            problems.append(f"controller_state={out.get('controller_state')!r}"
                            f" on an unstressed run (want 'steady')")
        if out.get("controller_actions") != 0:
            problems.append(f"controller_actions={out.get('controller_actions')}"
                            f" on an unstressed run (want 0)")
    else:
        storm = out["kill_storm"]
        if storm["lost_futures"]:
            problems.append(f"kill-storm lost {storm['lost_futures']} futures")
        if not storm["recovered_within_bound"]:
            problems.append(
                f"kill-storm recovery {storm['recovery_s']}s exceeded the "
                f"error-budget bound {storm['recovery_bound_s']}s "
                f"(state {storm['controller_state']})")
    if out["n_workers"] != (2 if SMOKE else max(1, out["n_workers"])):
        problems.append(f"n_workers={out['n_workers']}: fleet did not spawn")
    idle = [w for w, c in out["per_worker_sigs"].items() if c <= 0]
    if idle:
        problems.append(f"workers {idle} processed nothing: the router "
                        f"never dealt to them")
    if out["stitched_trace_depth"] < 2:
        problems.append(f"stitched_trace_depth="
                        f"{out['stitched_trace_depth']}: no trace crossed "
                        f"the node/worker seam")
    if SMOKE:
        if out["http_federated_families"] < 1:
            problems.append("no worker-labeled federated family on /metrics")
        if out["http_stitched_traces"] < 1:
            problems.append("no stitched cross-process trace on /traces")
        if out["http_request_timelines"] < 1:
            problems.append("no request lifecycle timelines on "
                            "/debug/requests")
    print(json.dumps(out))
    if problems:
        for p in problems:
            print(f"BENCH INVALID: {p}", file=sys.stderr)
        sys.exit(1)
    if GUARD:
        from corda_tpu.tools.benchguard import guard_multichip
        failures = guard_multichip(out)
        if failures:
            print("BENCH REGRESSION: fleet metrics breached their "
                  "trajectory floors:", file=sys.stderr)
            for p in failures:
                print(f"  {p}", file=sys.stderr)
            sys.exit(1)
        print("benchguard: ok", file=sys.stderr)


def ledger_main() -> None:
    """--ledger: the end-to-end ledger scenario (ISSUE 10): open-loop
    finance flows against the raft notary with the TPU verifier on the
    commit path. Smoke: tiny workload, chaos off, every signature batch
    under the host crossover — CPU tier-1 safe. Full: the measured shape
    with the chaos windows armed. Emits the LEDGER_r0*.json fields; the
    exactly-once and replica-agreement invariants are validity probes
    (BENCH INVALID), not guarded floors — a run that double-spends is
    wrong, not slow."""
    from corda_tpu.observability.ledger_harness import (
        LedgerScenarioConfig, ShardSweepConfig, run_ledger_scenario,
        run_shard_sweep_point, shard_scaling_fields)

    # --shards [N[,M...]] — the shard counts to sweep for the scaling
    # curve (default 1,2 smoke / 1,2,4 full; bare --shards keeps the
    # default).
    shard_counts = [1, 2] if SMOKE else [1, 2, 4]
    if "--shards" in sys.argv:
        i = sys.argv.index("--shards")
        if i + 1 < len(sys.argv) and not sys.argv[i + 1].startswith("-"):
            shard_counts = sorted({int(x) for x in
                                   sys.argv[i + 1].split(",") if x})
    top_shards = max(shard_counts)
    if SMOKE:
        # 2-shard CPU shape: tier-1 exercises the sharded provider +
        # cross-shard 2PC on every run (ISSUE 15 satellite). Small
        # compaction thresholds so every smoke run also proves the
        # bounded-log sawtooth and CoordinatorLog GC (ISSUE 20).
        cfg = LedgerScenarioConfig(shards=min(2, top_shards),
                                   cross_shard_pct=0.25,
                                   raft_snapshot_entries=4,
                                   coordlog_compact_bytes=1024)
    else:
        # The full flows scenario stays UNSHARDED: its fields carry
        # best-so-far floors fitted from the r01..r03 single-group
        # trajectory, and a sharded topology is a different workload
        # (smaller per-shard batches raise appends/tx by construction) —
        # comparing it against those floors would be guarding apples with
        # orange floors. Sharded end-to-end flows coverage lives in the
        # smoke shape (every tier-1 run), the scenario-tool preset, and
        # tests/test_chaos_sharded_notary.py; the sweep below is the
        # measured scaling story.
        cfg = LedgerScenarioConfig.full(chaos=True)
    out = run_ledger_scenario(cfg)
    out.pop("trace_sample", None)   # test hook, not an artifact field
    out["ledger"] = True
    out["sharded"] = True
    if SMOKE:
        out["smoke"] = True

    # the measured tx/s-vs-shards curve: notary-tier saturation per count
    # (the flows number above stays the headline committed_tx_per_sec so
    # the LEDGER trajectory remains comparable across rounds)
    points = []
    for n in shard_counts:
        sweep_cfg = ShardSweepConfig(
            shards=n, operations=220 if SMOKE else 1600,
            rate_tx_per_sec=600.0 if SMOKE else 1500.0,
            cross_shard_pct=0.08, chaos=(not SMOKE),
            seed=cfg.seed)
        points.append(run_shard_sweep_point(sweep_cfg))
    out.update(shard_scaling_fields(points))
    print(json.dumps(out))
    problems = []
    if not out["exactly_once_ok"]:
        problems.append("exactly-once violated: an accepted transaction's "
                        "inputs are not all consumed by that transaction "
                        "on every replica")
    if not out["replicas_agree"]:
        problems.append("raft replicas diverged at quiescence")
    if not out["counter_invariant_ok"]:
        problems.append("commit counters do not reconcile: committed != "
                        "notarised + self-issue (a committed tx either "
                        "passed the notary or had no inputs to check)")
    if out["stitched_traces"] < 1:
        problems.append("no connected flow.run→vault.update trace "
                        "(commit-path span stitching broken)")
    if out["ops_committed"] <= 0:
        problems.append("no operation committed")
    # blame conservation: the critical-path decomposition must account
    # for each class's e2e (runs under smoke too — the smoke gate is the
    # only CPU-tier proof the extractor still covers the whole path)
    from corda_tpu.tools.benchguard import ledger_critpath_violations
    problems.extend(ledger_critpath_violations(out))
    if out["stitched_traces"] >= 1 and out.get("ledger_critpath_traces", 0) < 1:
        problems.append("stitched traces exist but the critical-path "
                        "extractor decomposed none of them")
    # shard-sweep validity: every point must hold the safety invariants
    # (a sharded notary that double-spends or leaks reservations is
    # wrong, not slow), and multi-shard points must actually have run
    # cross-shard transactions through the 2PC
    for p in out.get("shard_sweep", []):
        tag = f"shard_sweep[shards={p.get('shards')}]"
        if not p.get("exactly_once_ok"):
            problems.append(f"{tag}: exactly-once violated")
        if not p.get("replicas_agree"):
            problems.append(f"{tag}: replicas diverged")
        if p.get("reserved_leftover", 0) != 0:
            problems.append(f"{tag}: {p['reserved_leftover']} refs left "
                            "reserved after in-doubt recovery")
        if p.get("shards", 1) > 1 and p.get("cross_shard_committed", 0) < 1:
            problems.append(f"{tag}: no cross-shard transaction committed")
    if out.get("ledger_shard_count", 1) > 1:
        if out.get("ledger_shard_cross_committed", 0) < 1:
            problems.append("flows scenario: no cross-shard tx committed")
        if out.get("ledger_shard_reserved_leftover", 0) != 0:
            problems.append("flows scenario: refs left reserved")
    if out.get("ledger_shard_finalize_conflicts", 0) != 0:
        problems.append("cross-shard atomicity violated: a finalize verdict "
                        "conflicted after the durable commit decision "
                        f"({out['ledger_shard_finalize_conflicts']} tx left "
                        "in-doubt)")
    # consensus-observatory validity (ISSUE 16): the per-entry raft
    # attribution must exist, the retained time-series plane must hold
    # ≥ 2 downsampled resolutions of Raft.LogEntries, and the sweep must
    # report a skew index. The attribution-sum conservation probe — the
    # component sum's p50 within 10% of the measured round p50 — is
    # enforced on FULL runs (hundreds of samples); under --smoke the
    # nearest-rank p50 of ~15 bimodal samples quantizes too coarsely for
    # a ratio test, so smoke only requires the fields to be live.
    attrib_sum = out.get("ledger_raft_attrib_sum_ms_p50", 0.0)
    round_p50 = out.get("ledger_raft_round_ms_p50", 0.0)
    if out.get("ledger_raft_attrib_samples", 0) < 1 or attrib_sum <= 0.0:
        problems.append("no raft commit-path attribution samples (the "
                        "consensus observatory saw no committed entry)")
    if round_p50 <= 0.0:
        problems.append("no measured consensus-round samples "
                        "(GroupCommitter.round_samples is empty)")
    if not SMOKE and attrib_sum > 0.0 and round_p50 > 0.0:
        rel = abs(attrib_sum - round_p50) / round_p50
        if rel > 0.10:
            problems.append(
                "raft attribution broke conservation: component sum p50 "
                f"{attrib_sum:.3f} ms vs measured round p50 "
                f"{round_p50:.3f} ms ({rel:.1%} apart, tolerance 10%)")
    if out.get("ledger_timeseries_resolutions", 0) < 2:
        problems.append("retained time-series plane holds "
                        f"{out.get('ledger_timeseries_resolutions', 0)} "
                        "downsampled resolutions of Raft.LogEntries "
                        "(want >= 2)")
    if out.get("shard_sweep_skew_index", 0.0) <= 0.0:
        problems.append("shard sweep reported no skew index")
    # bounded-state consensus (ISSUE 20): with compaction armed, replicas
    # must actually have snapshotted, and the RETAINED log must sawtooth
    # strictly under 2× the threshold — a peak at/over that bound means
    # compaction is not keeping up and the log is unbounded in disguise.
    snap_thr = out.get("ledger_raft_snapshot_threshold", 0)
    if snap_thr > 0:
        if out.get("ledger_raft_snapshots_taken", 0) < 1:
            problems.append("compaction armed "
                            f"(threshold {snap_thr}) but no replica took "
                            "a snapshot")
        log_peak = out.get("ledger_raft_log_entries_peak", 0)
        if log_peak >= 2 * snap_thr:
            problems.append(f"retained raft log peaked at {log_peak} "
                            f"entries against a {snap_thr}-entry snapshot "
                            "threshold (bounded-sawtooth invariant broken)")
        # the full chaos shape must additionally show the recovery paths
        # the smoke run is too small to force deterministically
        if not SMOKE and cfg.chaos:
            if out.get("ledger_raft_installs_received", 0) < 1:
                problems.append("chaos run with compaction: no lagging "
                                "follower caught up via InstallSnapshot")
            if out.get("ledger_raft_restarts", 0) < 1:
                problems.append("chaos run with compaction: no replica "
                                "crash-restart was executed")
    if problems:
        for p in problems:
            print(f"BENCH INVALID: {p}", file=sys.stderr)
        sys.exit(1)
    if GUARD:
        from corda_tpu.tools.benchguard import guard_ledger, guard_shards
        failures = guard_ledger(out) + guard_shards(out)
        if failures:
            print("BENCH REGRESSION: ledger metrics breached their "
                  "trajectory floors:", file=sys.stderr)
            for p in failures:
                print(f"  {p}", file=sys.stderr)
            sys.exit(1)
        print("benchguard: ok", file=sys.stderr)


def soak_main() -> None:
    """--soak: the drift-gated endurance run (ISSUE 19). Smoke: ~20 s of
    real load with every soak cadence accelerated (5 s phases, recurring
    chaos every 6 s) so tier-1 proves the full artifact schema — phase
    series, per-structure leak verdicts, subsystem CPU shares, drift
    slopes, mid-run invariant re-checks — without the wall clock. Full:
    ≥10 minutes at steady offered load over the sharded notary with
    chaos recurring on its schedule; emits the SOAK_r0*.json fields.

    Validity probes (BENCH INVALID, any shape): a ``leaking`` verdict on
    any declared-bounded structure, a failed mid-run invariant re-check,
    a missing schema field. Full runs additionally enforce the drift
    gates (throughput/p99 slope vs the declared bounds) and the CPU
    attribution sanity band (shares sum 90–110% of busy samples, a named
    top commit-path consumer) — a ~20 s smoke window is far too noisy
    for slope fits, exactly the existing smoke-vs-full benchguard
    discipline."""
    from corda_tpu.observability.soak import SoakConfig, run_soak

    minutes = 10.0
    if "--minutes" in sys.argv:
        i = sys.argv.index("--minutes")
        if i + 1 < len(sys.argv):
            minutes = float(sys.argv[i + 1])
    cfg = SoakConfig.smoke() if SMOKE else SoakConfig(minutes=minutes)
    out = run_soak(cfg)
    out.pop("trace_sample", None)
    out["ledger"] = True
    out["soak"] = True
    if SMOKE:
        out["smoke"] = True
    print(json.dumps(out))

    problems = []
    from corda_tpu.tools.benchguard import SOAK_REQUIRED
    missing = [k for k in SOAK_REQUIRED if k not in out]
    if missing:
        problems.append(f"soak artifact missing fields: {missing}")
    if not out.get("exactly_once_ok"):
        problems.append("exactly-once violated at quiescence")
    if not out.get("replicas_agree"):
        problems.append("raft replicas diverged at quiescence")
    if not out.get("soak_invariant_ok"):
        bad = [c for c in out.get("soak_invariant_checks", [])
               if not c.get("ok")]
        problems.append(f"mid-run invariant re-check failed: {bad}")
    if out.get("soak_leaking"):
        for name in out["soak_leaking"]:
            v = out["soak_leak_verdicts"].get(name, {})
            problems.append(
                f"leak verdict on declared-bounded structure {name}: "
                f"slope {v.get('slope_per_s')}/s, projected doubling "
                f"{v.get('doubling_s')}s")
    missing_verdicts = [n for n, v in
                        out.get("soak_leak_verdicts", {}).items()
                        if v.get("verdict") not in
                        ("bounded", "growing", "leaking")]
    if missing_verdicts:
        problems.append(f"structures without a leak verdict: "
                        f"{missing_verdicts}")
    if out.get("soak_cpu_samples", 0) < 1:
        problems.append("CPU profiler took no samples")
    if len(out.get("soak_phases", [])) < 2:
        problems.append("fewer than 2 soak phases sealed")
    if out.get("soak_chaos_cycles", 0) < 1:
        problems.append("no recurring chaos window ran")
    if not SMOKE:
        cpu_sum = out.get("soak_cpu_share_sum_pct", 0.0)
        if not 90.0 <= cpu_sum <= 110.0:
            problems.append(f"CPU shares sum to {cpu_sum}% of sampled "
                            "busy time (want 90–110%)")
        if not out.get("soak_cpu_top_commit_path"):
            problems.append("no top commit-path CPU consumer attributed")
        if not out.get("soak_drift_ok"):
            problems.append(
                "drift gate breached: throughput slope "
                f"{out.get('soak_throughput_slope_pct_per_min')}%/min "
                f"(gate ≥ {out.get('soak_throughput_gate_pct_per_min')}), "
                f"p99 slope {out.get('soak_p99_slope_pct_per_min')}%/min "
                f"(gate ≤ {out.get('soak_p99_gate_pct_per_min')})")
    if problems:
        for p in problems:
            print(f"BENCH INVALID: {p}", file=sys.stderr)
        sys.exit(1)
    if GUARD:
        from corda_tpu.tools.benchguard import guard_soak
        failures = guard_soak(out)
        if failures:
            print("BENCH REGRESSION: soak metrics breached their "
                  "trajectory floors:", file=sys.stderr)
            for p in failures:
                print(f"  {p}", file=sys.stderr)
            sys.exit(1)
        print("benchguard: ok", file=sys.stderr)


def main() -> None:
    from corda_tpu.observability import get_profiler
    from corda_tpu.verifier.batcher import SignatureBatcher
    # fresh flight-recorder counters: this run's compiles/occupancy/overlap
    # only (the profiler is process-global and always on)
    get_profiler().reset()
    items = make_items(BATCH)
    ed_items = make_ed_items(BATCH)
    r1_items = make_items(BATCH, ecmath.SECP256R1)
    if SMOKE:
        # host-crossover route only: no device kernel compiles on the
        # wiring check; kernel-rate fields keep their slots at 0.0
        dev = ed_dev = r1_dev = r1_fallback_pct = 0.0
    else:
        dev = device_rate(items)
        ed_dev = ed_device_rate(ed_items)
        r1_dev, r1_fallback_pct = r1_device_rate(r1_items)
    svc = service_metrics(items, ed_items, r1_items)
    host = host_baseline_rate(items[: min(128, BATCH)])

    def _ratio(service, kernel):
        # service throughput as a fraction of the raw kernel rate — the
        # continuous-batching headline (≥0.9 target). 0.0 in smoke (kernel
        # rates aren't measured there) so benchguard skips it.
        return round(service / kernel, 4) if kernel > 0 else 0.0

    out = {
        "metric": "ecdsa_secp256k1_verifies_per_sec_per_chip",
        "value": round(dev, 1),
        "unit": "verifies/s",
        "vs_baseline": round(dev / host, 3),
        "ed25519_verifies_per_sec_per_chip": round(ed_dev, 1),
        "secp256r1_verifies_per_sec_per_chip": round(r1_dev, 1),
        "r1_halfgcd_fallback_pct": round(r1_fallback_pct, 4),
        "r1_doublings_per_op": R1_DOUBLINGS_PER_OP,
        "service_path_verifies_per_sec": round(svc["k1_rate"], 1),
        "ed25519_service_path_verifies_per_sec": round(svc["ed_rate"], 1),
        "secp256r1_service_path_verifies_per_sec": round(svc["r1_rate"], 1),
        "mixed_service_path_verifies_per_sec": round(svc["mixed_rate"], 1),
        "service_to_kernel_ratio_k1": _ratio(svc["k1_rate"], dev),
        "service_to_kernel_ratio_ed25519": _ratio(svc["ed_rate"], ed_dev),
        "service_to_kernel_ratio_r1": _ratio(svc["r1_rate"], r1_dev),
        "tx_verify_p50_ms_batch1": round(svc["p50_ms"], 3),
        "tx_verify_p50_ms_batch1k": round(svc["p50_1k_ms"], 3),
        "tx_verify_p90_ms_batch1k": round(svc["p90_1k_ms"], 3),
        "tx_verify_p99_ms_batch1k": round(svc["p99_1k_ms"], 3),
        "host_baseline_verifies_per_sec": round(host, 1),
        "unique_signatures": UNIQUE,
        "prep_workers": SignatureBatcher.PREP_WORKERS,
        "prep_inflight_depth": SignatureBatcher.MAX_IN_FLIGHT,
        "prep_overlap_max": svc["overlap"],
        "post_warmup_compiles": svc["post_warmup_compiles"],
        "bucket_ladder": svc["bucket_ladder"],
        "interactive_latency_ms": svc["interactive_latency_ms"],
        "interactive_batch": svc["interactive_batch"],
        **svc["stages"],
    }
    # flight-recorder fields (corda_tpu.observability.profiling): where the
    # wall time went — XLA compiles vs cached dispatches, how full the
    # padded device batches ran, and how much host prep overlapped device
    # work. benchguard schema-locks these; the values are diagnostics.
    prof = get_profiler()
    totals = prof.compile_totals()
    out["compile_s_total"] = round(totals["compile_s_total"], 3)
    out["compile_cache_hits"] = totals["compile_cache_hits"]
    out["occupancy_pct_per_scheme"] = prof.occupancy_pct_per_scheme()
    out["prep_overlap_pct"] = round(prof.overlap.snapshot()["overlap_pct"], 2)
    if SMOKE:
        out["smoke"] = True
        # pipeline-serialization tripwires, cheap enough for tier-1: the
        # smoke run stays on the host route (no device intervals, so
        # overlap_pct is 0 by construction) — concurrent flushes on the
        # prep pool (PrepActive high-water ≥ 2) are its overlap signal,
        # and the hot-cache discipline must show ZERO compiles after
        # mark_warm(). A full bench run asserts the real overlap_pct via
        # benchguard instead.
        problems = []
        if out["prep_overlap_max"] < 2:
            problems.append(
                f"prep_overlap_max={out['prep_overlap_max']} < 2: scheme "
                f"flushes serialized — continuous planner not overlapping")
        if out["post_warmup_compiles"] != 0:
            problems.append(
                f"post_warmup_compiles={out['post_warmup_compiles']} != 0: "
                f"steady state recompiled after warmup")
        if problems:
            print(json.dumps(out))
            for p in problems:
                print(f"BENCH INVALID: {p}", file=sys.stderr)
            sys.exit(1)
    print(json.dumps(out))
    if GUARD:
        from corda_tpu.tools.benchguard import guard_current
        problems = guard_current(out)
        if problems:
            print("BENCH REGRESSION: guarded metrics breached their "
                  "trajectory floors:", file=sys.stderr)
            for p in problems:
                print(f"  {p}", file=sys.stderr)
            sys.exit(1)
        print("benchguard: ok", file=sys.stderr)


def require_tpu() -> None:
    """The measured modes report device numbers: refuse to produce them on
    any other backend (--smoke is the CPU wiring check and keeps its own
    names)."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"BENCH INVALID: no TPU found (platform {dev.platform!r}, "
              f"{dev.device_kind}); only --smoke runs without one",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    if not SMOKE:
        require_tpu()
    if FLEET:
        fleet_main()
    elif SOAK:
        soak_main()
    elif LEDGER:
        ledger_main()
    else:
        main()
